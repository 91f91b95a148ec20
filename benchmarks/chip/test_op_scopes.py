"""CPU tests of op_scopes.py and the readers built on it: the xplane
metadata reader, ops by scope and launch, the idle gaps by the program's
innermost span, the six readers on synthetic events, on a trace recorded
on a v5e (traces/), and the five older readers' values beside the
program's spans."""

from __future__ import annotations

import lzma
import sys
import types
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import cell  # noqa: E402
import harness  # noqa: E402
import op_scopes as osc  # noqa: E402
import peaks  # noqa: E402
import trace_reduce as tr  # noqa: E402

D0, H = "/device:TPU:0", tr.HOST_PLANE
NEW = ("decode_kv_write_ms.serve", "decode_head_ms.serve",
       "decode_attn_ms.serve", "prefill_attn_ms.serve",
       "host_batch_ms.serve", "host_sample_ms.serve")
OLD = ("queue_wait_mean_ms.serve", "decode_step_ms.serve",
       "prefill_step_ms.serve", "mfu.serve", "step_idle_share.serve")
RECORDED = HERE / "traces" / "stablelm-1.6b-2layers.v5e.xplane.pb.xz"


def _ev(plane, line, name, s, e):
    return tr.Event(plane, line, name, s, e)


def _op(name, s, e):
    return _ev(D0, tr.OPS_LINE, name, s, e)


KV = "%fusion.1 = s32[16,8]{1,0} scatter(s32[4,8] %p)"
ATT = "%ulppack_attention_decode.2 = f32[16,8]{1,0} custom-call()"
CPY = "%copy.3 = s32[4,8]{1,0} copy(s32[4,8]{0,1} %caches)"
HEAD = "%fusion.9 = bf16[16,512]{1,0} fusion(%x)"
BARE = "%copy.5 = s32[8]{0} copy(s32[8]{0} %y)"
COND = "%cond.4 = f32[16,8]{1,0} conditional(%z)"
INNER = "%fusion.7 = f32[16,8]{1,0} fusion(%z)"
MLP = "%fusion.8 = bf16[16,64]{1,0} fusion(%h)"

#: launches: decode [0, 100), prefill [200, 400), decode [500, 600)
DEVICE = [
    _ev(D0, tr.MODULES_LINE, "jit_decode_step(7)", 0, 100),
    _ev(D0, tr.MODULES_LINE, "jit_prefill_chunk_step(9)", 200, 400),
    _ev(D0, tr.MODULES_LINE, "jit_decode_step(7)", 500, 600),
    *[_op(n, t + s, t + e) for t in (0, 500) for n, s, e in (
        (KV, 0, 20), (ATT, 20, 50), (CPY, 50, 70), (HEAD, 70, 90),
        (BARE, 90, 100))],
    _op(COND, 200, 300), _op(INNER, 210, 250), _op(MLP, 300, 380),
    _op(BARE, 450, 460),                       # outside every launch
]
META = {
    (7, KV): osc.op_info("jit(decode_step)/layer_0/attn/kv_write/scatter:"),
    (7, ATT): osc.op_info("jit(decode_step)/layer_0/attn/core/"
                          "ulppack_attention_decode/pallas_call:"),
    (7, CPY): osc.op_info("caches[0]['attn']['k']:", "s32[4,8]{1,0}"),
    (7, HEAD): osc.op_info("jit(decode_step)/head/dot_general:"),
    (9, COND): osc.op_info("jit(prefill_chunk_step)/layer_0/attn/core/"
                           "cond:"),
    (9, INNER): osc.op_info("jit(prefill_chunk_step)/layer_0/attn/core/"
                            "mul:"),
    (9, MLP): osc.op_info("jit(prefill_chunk_step)/layer_0/mlp/add:"),
}


def _host(name, s, e):
    return _ev(H, "python", name, s, e)


#: three ticks; each pass's children nest inside it
HOST = [
    _host("bench.step", 0, 130), _host("engine.step", 0, 125),
    _host("engine.decode_pass", 0, 120), _host("engine.batch", 0, 4),
    _host("engine.launch.decode", 4, 6), _host("engine.logits", 6, 110),
    _host("engine.sample", 110, 120),
    _host("bench.step", 130, 430), _host("engine.step", 130, 425),
    _host("engine.prefill_pass", 130, 420), _host("engine.batch", 130, 196),
    _host("engine.cow", 140, 190), _host("engine.launch.prefill", 196, 200),
    _host("engine.logits", 200, 410), _host("engine.sample", 410, 420),
    _host("bench.step", 430, 630), _host("engine.step", 430, 625),
    _host("engine.decode_pass", 430, 620), _host("engine.batch", 430, 498),
    _host("engine.launch.decode", 498, 500), _host("engine.logits", 500, 600),
    _host("engine.sample", 600, 620),
]


@pytest.fixture
def synthetic():
    """A reader context whose trace is the synthetic events above."""
    events = DEVICE + HOST
    ops, mods = osc.device_ops(events, META)
    return types.SimpleNamespace(events=events, scoped=(events, ops, mods))


def read(name, ctx):
    return harness.load_reader(name)(ctx)


# ---------------------------------------------------------------------------
# scopes, launches, shares
# ---------------------------------------------------------------------------

def test_scope_of_an_op_from_its_tf_op():
    info = osc.op_info("jit(decode_step)/jit(main)/layer_3/attn/kv_write/"
                       "scatter:", "s32[4]")
    assert info == osc.OpInfo(("layer_3", "attn", "kv_write"), "", "s32[4]")
    assert osc.op_info("caches[3]['attn']['k']:") == \
        osc.OpInfo((), "caches[3]['attn']['k']", "")
    assert osc.op_info("jit(decode_step)/reshape:").scope == ()
    assert osc.op_info(None) == osc.OpInfo((), "", "")


def test_ops_take_their_launch_and_scope():
    ops, mods = osc.device_ops(DEVICE, META)
    assert [m.name for m in mods] == ["jit_decode_step(7)",
                                      "jit_prefill_chunk_step(9)",
                                      "jit_decode_step(7)"]
    by = {(o.start_ns, o.base): o for o in ops}
    assert by[(520, "ulppack_attention_decode")].launch == 2
    assert by[(520, "ulppack_attention_decode")].program == "jit_decode_step"
    assert by[(450, "copy")].program == "" and by[(450, "copy")].launch == -1
    # no metadata: the shape is read from the HLO text
    assert by[(90, "copy")].shape == "s32[8]"
    assert osc.under(by[(0, "fusion")], "kv_write")
    assert osc.under(by[(200, "cond")], "attn/core")
    assert not osc.under(by[(300, "fusion")], "attn/core")


def test_time_per_launch_under_a_scope():
    ops, mods = osc.device_ops(DEVICE, META)
    dec, pre = cell_programs()
    ms = osc.per_launch_ms
    assert ms(ops, mods, dec, lambda o: osc.under(o, "kv_write")) == \
        pytest.approx(20e-6)
    assert ms(ops, mods, dec, lambda o: o.base == "ulppack_attention_decode"
              ) == pytest.approx(30e-6)
    # a nested op counts once: [200, 300) holds [210, 250)
    assert ms(ops, mods, pre, lambda o: osc.under(o, "attn/core")) == \
        pytest.approx(100e-6)
    assert ms(ops, mods, pre, lambda o: osc.under(o, "head")) is None
    assert ms(ops, mods, "jit_nothing", lambda o: True) is None


def cell_programs():
    import readers
    return readers.DECODE_PROGRAM, readers.PREFILL_PROGRAM


def test_named_and_attributed_shares():
    ops, _ = osc.device_ops(DEVICE, META)
    dec, pre = cell_programs()
    # decode busy 100 per launch: kv_write 20, kernel 30, head 20 named;
    # the argument copy 20 attributed by its name; the bare copy 10 neither
    assert osc.named_share(ops, dec) == pytest.approx(0.7)
    assert osc.named_share(ops, dec, osc.attributed) == pytest.approx(0.9)
    assert osc.named_share(ops, pre) == pytest.approx(1.0)
    assert osc.named_share(ops, "jit_nothing") is None


def test_copies_by_site():
    ops, _ = osc.device_ops(DEVICE, META)
    sites = osc.seconds_by_site(ops)
    assert sites[0][:2] == ["jit_decode_step:operand caches[*]['attn']['k']",
                            pytest.approx(40e-9)]
    assert [s[0] for s in sites[1:]] == ["jit_decode_step:shape s32[8]",
                                         "no launch:shape s32[8]"]
    assert sum(s[2] for s in sites) == pytest.approx(1.0)


def test_idle_time_goes_to_the_innermost_program_span():
    events = DEVICE + HOST
    # idle [100, 200), [380, 450), [460, 500), [600, 630) of the host
    # extent [0, 630), each nanosecond to its innermost covering span
    idle = dict(osc.idle_by_span(events))
    assert idle == {"engine.cow": pytest.approx(50e-9),
                    "engine.batch": pytest.approx(74e-9),
                    "engine.logits": pytest.approx(40e-9),
                    "engine.sample": pytest.approx(40e-9),
                    "engine.launch.prefill": pytest.approx(4e-9),
                    "engine.launch.decode": pytest.approx(2e-9),
                    "engine.step": pytest.approx(15e-9),
                    "no host span": pytest.approx(15e-9)}
    assert sum(idle.values()) == pytest.approx(240e-9)
    assert osc._subtract([(0, 10), (20, 30)], [(5, 22), (25, 26)]) == \
        [(0, 5), (22, 25), (26, 30)]


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------

def test_the_six_readers_on_synthetic_events(synthetic):
    got = {name: read(name, synthetic) for name in NEW}
    assert got == {
        "decode_kv_write_ms.serve": pytest.approx(20e-6),
        "decode_head_ms.serve": pytest.approx(20e-6),
        "decode_attn_ms.serve": pytest.approx(30e-6),
        "prefill_attn_ms.serve": pytest.approx(100e-6),
        # batch spans 4 + 66 + 68 ns over three engine.step spans
        "host_batch_ms.serve": pytest.approx(138e-6 / 3),
        "host_sample_ms.serve": pytest.approx(40e-6 / 3)}


def test_readers_with_nothing_to_read_return_none():
    """A program without the scopes, kernel names and spans (the parent
    of this change): every new reader returns None and none raises."""
    kernel = "%decode_step.2 = f32[16,8]{1,0} custom-call()"
    events = [_op(kernel, e.start_ns, e.end_ns) if e.name == ATT else e
              for e in DEVICE] + [e for e in HOST if e.name in cell.SPANS]
    ops, mods = osc.device_ops(events, {})
    ctx = types.SimpleNamespace(events=events, scoped=(events, ops, mods))
    assert {name: read(name, ctx) for name in NEW} == dict.fromkeys(NEW)


def test_without_a_trace_of_its_own_a_reader_returns_none(tmp_path,
                                                          monkeypatch):
    monkeypatch.setattr(osc, "TRACE_ROOT", tmp_path)
    assert {name: read(name, types.SimpleNamespace(events=DEVICE + HOST))
            for name in NEW} == dict.fromkeys(NEW)
    cpu = types.SimpleNamespace(events=HOST)     # no device plane
    assert osc.traced(cpu) is None


def old_context(events):
    """The five older readers' context, as cell.trace_context builds it
    (host spans filtered to cell.SPANS)."""
    view = {"num_layers": 2, "d_model": 64, "num_heads": 4,
            "num_kv_heads": 4, "d_ff": 128, "vocab_size": 512,
            "head_dim": None}
    launch = {"kind": "decode", "index": np.array([5, 9]),
              "valid": np.array([1, 1]), "last": np.array([True, True])}
    rec = types.SimpleNamespace(admit=1.5, due=1.0)
    return types.SimpleNamespace(
        events=[e for e in events if e.plane != H or e.name in cell.SPANS],
        launches=[launch], view=view, peaks=peaks.peaks_for("TPU v5 lite"),
        records=[rec], traced_s=1e-6)


def test_the_older_readers_read_the_same_beside_the_program_spans():
    """The program's spans repeat the benchmark's wrapper spans under the
    same names (nested one inside the other): the five older readers and
    the idle-gap breakdown read what they read without them."""
    wrappers = DEVICE + [e for e in HOST if e.name in cell.SPANS]
    both = DEVICE + HOST + [e for e in HOST if e.name in cell.SPANS]
    before, after = old_context(wrappers), old_context(both)
    for name in OLD:
        assert read(name, before) == read(name, after), name
    assert read("step_idle_share.serve", before) is not None
    window = (0, 630)
    assert tr.idle_gaps(before.events, window, cell.SPANS) == \
        tr.idle_gaps(after.events, window, cell.SPANS)


# ---------------------------------------------------------------------------
# the xplane's metadata, by the wire reader
# ---------------------------------------------------------------------------

def _varint(v):
    v &= (1 << 64) - 1
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        out.append(b | (0x80 if v else 0))
        if not v:
            return bytes(out)


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _stat(mid, value):
    """An XStat: a string, or an unsigned integer (as program ids are)."""
    if isinstance(value, str):
        return _field(1, mid) + _field(5, value)
    return _field(1, mid) + _varint(3 << 3) + _varint(value)


def _plane(name, events, stats):
    body = _field(1, 3) + _field(2, name)
    body += _field(3, _field(2, "XLA Ops") + _field(4, _field(1, 1)))
    for mid, (ev_name, display, ev_stats) in events.items():
        em = _field(1, mid) + _field(2, ev_name) + _field(4, display)
        for sid, val in ev_stats:
            em += _field(5, _stat(sid, val))
        body += _field(4, _field(1, mid) + _field(2, em))
    for sid, sname in stats.items():
        body += _field(5, _field(1, sid) + _field(2, _field(1, sid)
                                                  + _field(2, sname)))
    return body


def test_metadata_read_from_the_wire(tmp_path):
    stats = {1: "program_id", 2: "tf_op", 3: "deduplicated_name",
             4: "shape_with_layout"}
    events = {
        10: ("%copy.259 = f32[8]{0} copy(%a)", "copy.259",
             [(1, 11788519255109843533),
              (2, "jit(decode_step)/layer_0/attn/core/x/pallas_call:"),
              (4, "f32[8]{0}")]),
        11: ("%copy.282 = f32[8]{0} copy(%b)", "copy.282",
             [(1, 11788519255109843533), (3, "copy.259")]),
        12: ("%copy.1 = s32[4]{0} copy(%c)", "copy.1",
             [(1, 5), (2, "caches[2]['attn']['v']:")]),
    }
    space = _field(1, _plane("/host:CPU", {}, {}))
    space += _field(1, _plane(D0, events, stats))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space)
    meta = osc.read_metadata(str(path))
    pid = 11788519255109843533        # as the launch names it
    assert meta[(pid, "%copy.259 = f32[8]{0} copy(%a)")] == osc.OpInfo(
        ("layer_0", "attn", "core", "x"), "", "f32[8]{0}")
    # deduplicated: the scope of the op it copies
    assert meta[(pid, "%copy.282 = f32[8]{0} copy(%b)")].scope == \
        ("layer_0", "attn", "core", "x")
    assert meta[(5, "%copy.1 = s32[4]{0} copy(%c)")].operand == \
        "caches[2]['attn']['v']"


# ---------------------------------------------------------------------------
# a trace recorded on a v5e: the cell's engine at 2 layers, full widths,
# through cell.TraceHooks; 3 requests of 40 prompt tokens and 3 new tokens
# (two prefill ticks, two decode ticks, a copy-on-write per request)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("v5e") / "vm.xplane.pb"
    path.write_bytes(lzma.decompress(RECORDED.read_bytes()))
    return str(path)


def test_recorded_ops_fall_under_their_scopes(recorded):
    events = tr.load(recorded, host_names=set())
    ops, mods = osc.device_ops(events, osc.read_metadata(recorded))
    dec, pre = cell_programs()
    programs = [tr.module_name(m.name) for m in mods]
    assert programs.count(dec) == 2 and programs.count(pre) == 2
    bases = {o.base for o in ops}
    assert {"ulppack_matmul", "quantize_pack",
            "ulppack_attention_decode"} <= bases
    assert "decode_step" not in bases
    att = [o for o in ops if o.base == "ulppack_attention_decode"]
    assert att and all(o.program == dec and osc.under(o, "attn/core")
                       for o in att)
    assert {o.scope[0] for o in att} == {"layer_0", "layer_1"}
    for scope in ("embed", "attn/qkv", "attn/kv_write", "attn/core",
                  "attn/out", "mlp", "head"):
        assert osc.per_launch_ms(ops, mods, dec,
                                 lambda o, s=scope: osc.under(o, s)) > 0
    assert osc.named_share(ops, dec, osc.attributed) >= 0.9


def test_recorded_spans_nest_and_name_each_copy_on_write(recorded):
    events = tr.load(recorded, host_names=set(osc.PROGRAM_SPANS))
    host = [e for e in events if e.plane == tr.HOST_PLANE]

    def spans(name):
        return [e for e in host if e.name == name]

    def inside(e, parents):
        return any(p.start_ns <= e.start_ns and e.end_ns <= p.end_ns
                   for p in parents)

    passes = spans("engine.prefill_pass") + spans("engine.decode_pass")
    assert len(spans("engine.cow")) == 3
    assert all(inside(e, spans("engine.batch")) for e in spans("engine.cow"))
    for name in ("engine.batch", "engine.launch.prefill",
                 "engine.launch.decode", "engine.logits", "engine.sample"):
        assert spans(name) and all(inside(e, passes) for e in spans(name))
    assert all(inside(e, spans("engine.step")) for e in passes)
    assert {g[0] for g in osc.idle_by_span(events)} & {
        "engine.batch", "engine.cow", "engine.logits", "engine.sample"}


def test_the_six_readers_on_the_recorded_trace(recorded, tmp_path,
                                               monkeypatch):
    events = tr.load(recorded, host_names=set(cell.SPANS))
    named = {n: read(n, types.SimpleNamespace(events=events,
                                              xplane=recorded)) for n in NEW}
    assert all(isinstance(v, float) and v > 0 for v in named.values())
    # the kernel is a part of its scope's time
    ops, mods = osc.device_ops(events, osc.read_metadata(recorded))
    core = osc.per_launch_ms(ops, mods, cell_programs()[0],
                             lambda o: osc.under(o, "attn/core"))
    assert named["decode_attn_ms.serve"] < core
    # found by its launches where the context does not name it
    where = tmp_path / "trace" / "stablelm-1.6b.chat" / "plugins"
    where.mkdir(parents=True)
    (where / "vm.xplane.pb").write_bytes(Path(recorded).read_bytes())
    monkeypatch.setattr(osc, "TRACE_ROOT", tmp_path / "trace")
    found = {n: read(n, types.SimpleNamespace(events=events)) for n in NEW}
    assert found == named
    other = types.SimpleNamespace(events=DEVICE + HOST)
    assert osc.traced(other) is None
