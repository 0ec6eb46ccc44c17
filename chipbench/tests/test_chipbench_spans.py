"""The program-span reading on small constructed traces: idle gaps given
to the innermost span of either kind, the device clock shift, span args
summed, device time by named scope from op metadata, and every reader of
a program span or scope silent on a trace without them."""
import os
from types import SimpleNamespace

import pytest
from jax.profiler import ProfileData

from chipbench import harness, spans

RUN_ID, TF_OP = 99, 98

# device ops: (HLO text, start ns, end ns, tf_op stat or None, whether
# the stat sits on the event's metadata rather than on the event)
OPS = [("%fusion.1 = f32[8] fusion(x)", 1000, 3000,
        "jit(reduce_blocktopk_step)/reduce/pack/concatenate", True),
       ("%scatter.2 = f32[8] scatter(y), metadata={op_name="
        "\"jit(reduce_blocktopk_step)/reduce/segment_sum/scatter-add\"}",
        3000, 4000, None, False),
       ("%fusion.3 = f32[8] fusion(z)", 4000, 5000,
        "jit(reduce_blocktopk_step)/reduce/residual/scatter-add:Scatter",
        False),
       ("%fusion.4 = f32[8] fusion(w)", 6000, 8000,
        "jit(reduce_blocktopk_step)/reduce/optimizer/sqrt", True)]
MODULE = ("jit_reduce_blocktopk_step(7)", 900, 9000)
# host spans: (name, start, end, {arg: int})
SPANS = [("cb:traced", 0, 20000, {}),
         ("cb:serve.step", 400, 9500, {}),
         ("mlitb.serve.step", 450, 9400, {"queued": 2}),
         ("mlitb.serve.admit", 600, 700, {"admitted": 1}),
         ("mlitb.serve.decode", 5000, 6100,
          {"rows_live": 2, "kv_pages_live": 3, "kv_pages_walked": 16}),
         ("mlitb.serve.readback", 5800, 6100, {}),
         ("mlitb.serve.decode", 8000, 9300,
          {"rows_live": 2, "kv_pages_live": 5, "kv_pages_walked": 16}),
         ("mlitb.train.step", 12000, 13000, {"n_live": 4}),
         ("mlitb.train.mask", 12000, 12500, {}),
         ("mlitb.train.readback", 12500, 13000, {}),
         ("not-ours", 0, 20000, {})]


def _stat(key, value):
    if isinstance(value, str):
        return f'stats {{ metadata_id: {key} str_value: "{value}" }}'
    return f"stats {{ metadata_id: {key} int64_value: {value} }}"


def _events(items, first_md):
    """(events, event metadata, stat metadata) of [(name, start, end,
    [(stat name, value)], [(stat name, value) of the metadata])]."""
    ev, md, names = [], [], {}

    def stats(pairs):
        out = []
        for sname, v in pairs:
            sid = {"run_id": RUN_ID, "tf_op": TF_OP}.get(sname)
            if sid is None:
                sid = names.setdefault(sname, 200 + first_md + len(names))
            out.append(_stat(sid, v))
        return " ".join(out)

    for i, (name, s, e, st, md_st) in enumerate(items):
        k = first_md + i
        ev.append(f"events {{ metadata_id: {k} offset_ps: {s * 1000} "
                  f"duration_ps: {(e - s) * 1000} {stats(st)} }}")
        quoted = name.replace("\\", "\\\\").replace('"', '\\"')
        md.append(f'event_metadata {{ key: {k} value {{ id: {k} '
                  f'name: "{quoted}" {stats(md_st)} }} }}')
    smd = [f'stat_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
           for n, i in names.items()]
    return ev, md, smd


def _xspace(enqueue_end=800, ops=OPS, spans_=SPANS, module=MODULE):
    ops_ev, ops_md, _ = _events(
        [(t, s, e, [] if op is None or on_md else [("tf_op", op)],
          [("tf_op", op)] if on_md else []) for t, s, e, op, on_md in ops],
        1)
    mod_ev, mod_md, _ = _events([module + ([("run_id", 1)], [])], 50)
    span_ev, span_md, span_smd = _events(
        [(n, s, e, list(a.items()), []) for n, s, e, a in spans_], 60)
    enq_ev, enq_md, _ = _events([("DoEnqueueProgram", enqueue_end - 100,
                                  enqueue_end, [("run_id", 1)], [])], 180)
    fixed = (f'stat_metadata {{ key: {RUN_ID} value {{ id: {RUN_ID} '
             f'name: "run_id" }} }} stat_metadata {{ key: {TF_OP} value '
             f'{{ id: {TF_OP} name: "tf_op" }} }}')
    txt = f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {' '.join(ops_ev)} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0 {' '.join(mod_ev)} }}
  {' '.join(ops_md + mod_md)} {fixed} }}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "main" timestamp_ns: 0 {' '.join(span_ev)} }}
  lines {{ id: 2 name: "queue" timestamp_ns: 0 {' '.join(enq_ev)} }}
  {' '.join(span_md + enq_md + span_smd)} {fixed} }}
"""
    return ProfileData.text_proto_to_serialized_xspace(txt)


def _reading(**kw):
    return spans.from_xspace(_xspace(**kw))


def _ctx(tmp_path, **kw):
    d = tmp_path / "trace"
    prof = d / "plugins" / "profile" / "2026_01_01_00_00_00"
    os.makedirs(prof)
    (prof / "host.xplane.pb").write_bytes(_xspace(**kw))
    return SimpleNamespace(run=SimpleNamespace(trace_dir=str(d)))


def test_gap_under_a_program_span_in_a_harness_span_goes_to_it():
    r = _reading()
    assert r.window_s == pytest.approx(20e-6)
    # busy [1000, 5000] and [6000, 8000]: the idle [0, 1000] has its
    # middle under mlitb.serve.step (inside cb:serve.step), [5000, 6000]
    # under the first mlitb.serve.decode (its readback starts after the
    # middle), and [8000, 20000] under no span
    assert r.gaps == {"mlitb.serve.step": pytest.approx(1e-6),
                      "mlitb.serve.decode": pytest.approx(1e-6),
                      "(no span)": pytest.approx(12e-6)}
    # in percent of the window, by program span names
    assert r.gap_share(["serve.step", "serve.decode"]) == pytest.approx(10.0)
    assert r.gap_share(["serve.readback"]) == pytest.approx(0.0)


def test_device_clock_moves_to_the_host_enqueue():
    # the program cannot start (device 900) before its enqueue ended
    # (host 1400): every device time moves 500 ns later, so the idle
    # [5500, 6500] has its middle 6000 under the readback
    r = _reading(enqueue_end=1400)
    assert r.gaps["mlitb.serve.readback"] == pytest.approx(1e-6)
    assert r.gaps["mlitb.serve.step"] == pytest.approx(1.5e-6)  # [0, 1500]
    assert "mlitb.serve.decode" not in r.gaps


def test_span_args_are_summed_over_the_window():
    r = _reading()
    assert r.arg_sum("serve.decode", "kv_pages_live") == 8
    assert r.arg_sum("serve.decode", "kv_pages_walked") == 32
    assert r.arg_sum("serve.decode", "absent") is None
    assert r.median_ms("serve.decode") == pytest.approx(1.2e-3)
    assert [s.args["queued"] for s in r.named("serve.step")] == [2]
    # a span outside cb:traced is not read
    out = SPANS[:-1] + [("mlitb.serve.decode", 20500, 21000,
                         {"kv_pages_live": 100, "kv_pages_walked": 100})]
    assert _reading(spans_=out).arg_sum("serve.decode",
                                        "kv_pages_live") == 8


def test_scope_shares_come_from_op_metadata():
    r = _reading()
    prog = "jit_reduce_blocktopk_step"
    # the tf_op stat of the event or of its metadata (less a ":type"),
    # else the HLO text's op_name
    assert r.scope_s[(prog, "jit(reduce_blocktopk_step)/reduce/residual/"
                      "scatter-add")] == pytest.approx(1e-6)
    assert r.scope_s[(prog, "jit(reduce_blocktopk_step)/reduce/"
                      "segment_sum/scatter-add")] == pytest.approx(1e-6)
    assert r.program_s == {prog: pytest.approx(8.1e-6)}
    scat = r.scope_share("jit_reduce_", ["reduce/residual",
                                         "reduce/segment_sum"])
    assert scat == pytest.approx(100 * 2 / 8.1)
    assert r.scope_share("jit_reduce_", ["reduce/pack"]) == \
        pytest.approx(100 * 2 / 8.1)
    assert spans.under("jit(f)/reduce/pack/mul", "reduce/pack")
    assert not spans.under("jit(f)/reduce/packed/mul", "reduce/pack")


READ = {"kv_live_share.serve": 100 * 8 / 32,
        "decode_ms.serve": 1.2e-3,
        "host_gap_share.serve": 10.0,
        "host_gap_share.train": 0.0,
        "scatter_share.reduce": 100 * 2 / 8.1,
        "pack_share.reduce": 100 * 2 / 8.1}


@pytest.mark.parametrize("name", sorted(READ))
def test_each_reader_reads_its_spans(tmp_path, name):
    got = harness.load_reader(name).read(_ctx(tmp_path))
    assert got == pytest.approx(READ[name])


@pytest.mark.parametrize("name", sorted(READ))
def test_each_reader_is_silent_without_its_spans(tmp_path, name):
    # what a program without spans leaves: the harness's spans only, and
    # a bare reducer program whose ops carry no scope path
    bare = [(t.split(", metadata")[0], s, e, None, False)
            for t, s, e, _, _ in OPS]
    ctx = _ctx(tmp_path, ops=bare,
               spans_=[sp for sp in SPANS if sp[0].startswith("cb:")],
               module=("jit_fn(7)", 900, 9000))
    assert harness.load_reader(name).read(ctx) is None


def test_a_run_without_a_trace_reads_nothing(tmp_path):
    ctx = SimpleNamespace(run=SimpleNamespace(trace_dir=str(tmp_path)))
    assert spans.read(ctx) is None
    assert harness.load_reader("decode_ms.serve").read(ctx) is None


def test_the_gap_table_prints_by_program_span(tmp_path, capsys):
    ctx = _ctx(tmp_path)
    assert spans.main([ctx.run.trace_dir]) == 0
    out = capsys.readouterr().out
    assert "| `mlitb.serve.step` | 0.000001 | 5.000 |" in out
    assert "`mlitb.serve.decode` | 2 |" in out
    assert spans.main([str(tmp_path / "none")]) == 1


def test_a_metadata_stat_may_name_its_value_by_reference():
    # XStat.ref_value points at the stat metadata whose name is the value
    txt = """
planes { id: 1 name: "/device:TPU:0"
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[8] fusion(x)"
    stats { metadata_id: 98 ref_value: 300 } } }
  event_metadata { key: 2 value { id: 2 name: "%copy.2 = f32[8] copy(x)" } }
  stat_metadata { key: 98 value { id: 98 name: "tf_op" } }
  stat_metadata { key: 300 value { id: 300 name: "jit(f)/reduce/pack/mul" } }
}
planes { id: 2 name: "/host:CPU"
  event_metadata { key: 1 value { id: 1 name: "x" stats {
    metadata_id: 98 str_value: "not a device op" } } }
  stat_metadata { key: 98 value { id: 98 name: "tf_op" } } }
"""
    raw = ProfileData.text_proto_to_serialized_xspace(txt)
    assert spans.metadata_stat(raw) == {
        "/device:TPU:0": {"%fusion.1 = f32[8] fusion(x)":
                          "jit(f)/reduce/pack/mul"}}
