"""The benchmark's frozen copy of the trace pool equals the program's
pool as it stood when the copy was made.  The copy is the yardstick's
own data; this test documents where it came from."""

from perfbench_util import load


def test_pool_knobs_match_the_program():
    from repro.core import perf_model
    tr = load("reference/traffic.py")
    pool = tr.pool()
    offs, rhs, wfs, ias = perf_model._pool_knobs()
    assert pool["offsets"] == list(offs)
    assert pool["row_hits"] == list(rhs)
    assert pool["write_fracs"] == list(wfs)
    assert pool["inter_arrivals_ns"] == list(ias)
    assert len(pool["names"]) == 70
