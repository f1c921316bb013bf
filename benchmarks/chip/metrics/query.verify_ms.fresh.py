"""query.verify_ms.fresh: ``query.verify_ms`` in the cells that report
``fresh_queries_per_s``, which it moves there."""
from pathlib import Path

from chipbench import spec

read = spec.reader("metrics", "query.verify_ms",
                   bench_dir=Path(__file__).resolve().parents[1])
