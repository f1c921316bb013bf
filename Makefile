# Single entry points for CI / local verification.
#
#   make check   — fast gate: tier-1 tests (tier2 deselected via pytest.ini)
#                  + quick store-scale bench + throughput-regression guard
#   make tier2   — the slow tests only (subprocess sharding, train-loop smoke)
#   make test    — everything (tier-1 + tier2)
#   make bench   — full benchmark suite (slow; trains the bench fixture)
#   make bench-index — IVF recall/throughput sweep (BENCH_index_scale.json)

PY := PYTHONPATH=src python

.PHONY: check tier1 tier2 test bench-quick guard bench bench-index

check: tier1 bench-quick guard

tier1:
	$(PY) -m pytest -x -q

tier2:
	$(PY) -m pytest -x -q -m tier2

test:
	$(PY) -m pytest -x -q -m ""

bench-quick:
	$(PY) -m benchmarks.store_scale --sizes 1000,10000 --mixed-repeats 2

guard:
	$(PY) -m benchmarks.check_regression

bench:
	$(PY) -m benchmarks.run

bench-index:
	XLA_FLAGS=--xla_force_host_platform_device_count=8 $(PY) -m benchmarks.index_scale
	$(PY) -m benchmarks.check_regression
