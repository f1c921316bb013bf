"""idle_share.query.fresh: ``idle_share.query`` in the cells that report
``fresh_queries_per_s``, which it moves there."""
from pathlib import Path

from chipbench import spec

read = spec.reader("metrics", "idle_share.query",
                   bench_dir=Path(__file__).resolve().parents[1])
