"""fresh_queries_per_s: ``queries_per_s`` in the cells whose drains refine
fresh rows in round 3. A metric of its own, so that its bound follows the
spread of those cells and not that of the host-bound cells without
refinement."""
from pathlib import Path

from chipbench import spec

read = spec.reader("end_to_end", "queries_per_s",
                   bench_dir=Path(__file__).resolve().parents[1])
