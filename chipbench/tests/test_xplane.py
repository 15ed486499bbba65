"""xplane.py against a small hand-made trace (trace_fixture.textproto):
one device, two programs, nested ops, events outside the window."""

import os

from chipbench import xplane

HERE = os.path.dirname(os.path.abspath(__file__))


def load():
    from jax.profiler import ProfileData

    with open(os.path.join(HERE, "trace_fixture.textproto")) as f:
        return ProfileData.from_text_proto(f.read())


def test_busy_idle_and_programs():
    r = xplane.reduce(load())
    # window: marker begin at 1,000 ns, marker end at 11,000 ns
    assert r["begin_ns"] == 1000 and r["end_ns"] == 11000
    assert r["window_ns"] == 10000
    # ops inside the window: [2000,4000) fusion.1, [3000,3500) nested
    # copy.2, [6000,7000) fusion.1, [9000,9500) sort.3; [500,900) lies
    # before the window, [10800,11500) is clipped to [10800,11000)
    assert r["devices"][0]["intervals"] == [
        [2000, 4000], [6000, 7000], [9000, 9500], [10800, 11000]]
    assert r["busy_ns"] == 2000 + 1000 + 500 + 200
    assert r["programs_ns"] == {"jit_multi_scan_kernel": 2000 + 1000,
                                "jit_masked_topk": 500}
    assert r["program_calls"] == {"jit_multi_scan_kernel": 2,
                                  "jit_masked_topk": 1}
    assert dict(r["ops_ns"])["fusion.1"] == 3000
    assert r["ops_ns"][0][0] == "fusion.1"


def test_idle_gaps_are_named_by_the_open_span():
    r = xplane.reduce(load())
    gaps = xplane.idle_gaps(r)
    assert gaps[0] == (4000, 6000) or gaps[0] == (7000, 9000)
    assert sum(b - a for a, b in gaps) == r["window_ns"] - r["busy_ns"]
    zero = 1_000_000          # wall clock at the profile's zero
    spans = [
        {"name": "HTTP GET /api/search", "span_id": "a", "parent_id": None,
         "start_ns": zero + 3900, "end_ns": zero + 9100},
        {"name": "batcher.Search", "span_id": "b", "parent_id": "a",
         "start_ns": zero + 3950, "end_ns": zero + 6100},
    ]
    named = dict(xplane.attribute_gaps(gaps, spans, zero))
    # [4000,6000) lies in both spans: the deeper one names it;
    # [7000,9000) only in the HTTP span; the rest has no span open
    assert named["batcher.Search"] == 2000 / 1e9
    assert named["HTTP GET /api/search"] == 2000 / 1e9
    assert abs(named["(no span open)"] - (1000 + 1300) / 1e9) < 1e-15


def test_merge_and_clip():
    assert xplane.merge([[5, 7], [1, 3], [2, 4], [7, 8]]) == [[1, 4], [5, 8]]
    assert xplane.clip([[0, 10], [20, 30]], 5, 25) == [[5, 10], [20, 25]]
    assert xplane.program_name("jit_scan_kernel(1234)") == "jit_scan_kernel"
