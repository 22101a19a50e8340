"""The reader of ``active_topk_tiles_per_query`` on the CPU: driver tiles
per live query row from the serve counters, and nothing where a program
has no such counter."""
import pytest

from chipbench import registry
from chipbench.context import Context


@pytest.mark.parametrize("counters, want", [
    ({"topk_tiles_scanned": 30, "topk_rows_live": 12}, 2.5),
    ({"topk_tiles_scanned": 0, "topk_rows_live": 0}, None),
    ({"queries_served": 5}, None),      # a program without the counter
])
def test_tiles_per_query_reads_the_serve_counters(counters, want):
    read = registry.reader("active_topk_tiles_per_query")
    ctx = Context(trace=None, counters=counters, window={}, shapes={},
                  peaks={})
    assert read(ctx) == want
