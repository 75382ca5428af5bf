"""Planned entries for reader files that no cell reports yet.

``bench_rehearsal.UNPROVEN`` lists the manifest entries that the serve
runner's cells WOULD have (they are rehearsed on the CPU under those
entries, and ``test_every_reader_is_found_by_name_or_by_stem`` allows no
reader file that neither a listed nor a planned metric finds).  The two
``program_span`` readers of the continuous engine's own spans
(``layer_metrics/sched_ms_per_wave.py``, ``harvest_wait_ms.py``) are
such files: planned here, for both serve cells, so that every traced
rehearsal of the serve runner reads them.  An addition, made where
pytest imports it before any test file of this directory.
"""

import bench_rehearsal as br

_ENGINE = br._LAYER
_PLANNED = [
    ("sched_ms_per_wave.serve", "ms", "program_span", _ENGINE, "ttft_p95_ms"),
    ("harvest_wait_ms.serve", "ms", "program_span", _ENGINE, "ttft_p95_ms"),
    ("sched_ms_per_wave.gen", "ms", "program_span", _ENGINE,
     "gen_tokens_per_s"),
    ("harvest_wait_ms.gen", "ms", "program_span", _ENGINE,
     "gen_tokens_per_s"),
]
for _entry in _PLANNED:
    if _entry not in br.UNPROVEN["per_layer"]:
        br.UNPROVEN["per_layer"].append(_entry)
