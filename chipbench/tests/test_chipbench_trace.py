"""The trace reduction on a small constructed trace: busy time as a union,
idle share, kernel and collective sums, and idle gaps given to the host
span that covers them."""
import pytest
from jax.profiler import ProfileData

from chipbench import trace

OPS = [("%fusion.1 = f32[8] fusion(x)", 1000, 3000),
       ("%all-reduce.2 = f32[8] all-reduce(y)", 2500, 5000),
       ('%flash_decode.3 = bf16[8] custom-call(q), '
        'custom_call_target="tpu_custom_call"', 6000, 8000)]
SPANS = [("cb:traced", 0, 20000), ("cb:step", 500, 9000),
         ("cb:inner", 5600, 6100), ("cb:wait", 9000, 20000),
         ("not-ours", 0, 20000)]


def _events(items, first_md, stat=None):
    out, md = [], []
    for i, (name, s, e) in enumerate(items):
        k = first_md + i
        extra = f" stats {{ metadata_id: 99 int64_value: {stat} }}" \
            if stat is not None else ""
        out.append(f"events {{ metadata_id: {k} offset_ps: {s * 1000} "
                   f"duration_ps: {(e - s) * 1000}{extra} }}")
        md.append(f"event_metadata {{ key: {k} value {{ id: {k} "
                  f"name: {name!r} }} }}".replace("'", '"')
                  if '"' not in name else
                  f"event_metadata {{ key: {k} value {{ id: {k} "
                  f"name: '{name}' }} }}")
    return out, md


def _profile(enqueue_end: int, ops=OPS):
    ops_ev, ops_md = _events(ops, 1)
    mod_ev, mod_md = _events([("jit_step(42)", 900, 9000)], 50, stat=1)
    span_ev, span_md = _events(SPANS, 60)
    enq_ev, enq_md = _events([("DoEnqueueProgram", enqueue_end - 100,
                               enqueue_end)], 80, stat=1)
    stat = 'stat_metadata { key: 99 value { id: 99 name: "run_id" } }'
    txt = f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {' '.join(ops_ev)} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0 {' '.join(mod_ev)} }}
  {' '.join(ops_md + mod_md)} {stat} }}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "main" timestamp_ns: 0 {' '.join(span_ev)} }}
  lines {{ id: 2 name: "queue" timestamp_ns: 0 {' '.join(enq_ev)} }}
  {' '.join(span_md + enq_md)} {stat} }}
"""
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(txt))


def test_busy_idle_kernels_collectives_and_gaps():
    s = trace.summarize(trace.from_profile(_profile(enqueue_end=800)))
    assert s.window_s == pytest.approx(20e-6)
    # union of [1000, 5000] and [6000, 8000]
    assert s.busy_s == pytest.approx(6e-6)
    assert s.idle_share == pytest.approx(0.7)
    assert s.kernel("flash_decode") == (pytest.approx(2e-6), 1)
    assert s.kernel("topk") == (0, 0)
    assert s.collective_s == pytest.approx(2.5e-6)
    # the all-reduce alone in [3000, 5000]
    assert s.collective_exposed_s == pytest.approx(2e-6)
    # [0, 1000] and [5000, 6000] under cb:step (cb:inner misses the middle
    # of the second), [8000, 20000] under cb:wait
    assert s.gaps == {"cb:step": pytest.approx(2e-6),
                      "cb:wait": pytest.approx(12e-6)}
    b = s.breakdown()
    assert b["device_ops"][0] == ["jit_step/all-reduce", pytest.approx(
        2.5e-6)]
    assert [g[0] for g in b["idle_gaps"]] == ["cb:wait", "cb:step"]


def test_device_clock_moves_to_the_host_enqueue():
    # the program cannot start (device 900) before its enqueue ended
    # (host 1400): every device time moves 500 ns later
    s = trace.summarize(trace.from_profile(_profile(enqueue_end=1400)))
    assert s.busy_s == pytest.approx(6e-6)
    assert s.gaps["cb:step"] == pytest.approx(1.5e-6)   # [0, 1500]
    assert s.gaps["cb:inner"] == pytest.approx(1e-6)    # [5500, 6500]


def test_interval_helpers():
    assert trace.union([(5, 6), (1, 3), (2, 4)]) == [(1, 4), (5, 6)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert trace.clip([(0, 4), (6, 9)], 2, 7) == [(2, 4), (6, 7)]


def test_a_trace_without_the_window_span_is_refused():
    t = trace.from_profile(_profile(800))
    t.spans = [sp for sp in t.spans if sp[0] != trace.WINDOW_SPAN]
    with pytest.raises(ValueError):
        trace.summarize(t)
