"""``bench/scopes.py``: an operation's ``op_name`` out of a trace, own
device time by scope, and the two readers built on them."""
import types

import pytest

from bench import scopes, trace
from bench.manifest import Manifest
from bench.trace import WINDOW_SPAN, Op, Summary
from conftest import REPO
from repro.runtime import spans

MS = 1_000_000
MAN = Manifest(REPO)
REMAT_SHARE = MAN.reader("remat_share")
DATA_WAIT_SHARE = MAN.reader("data_wait_share")

#: name stacks of the compiled step, as JAX writes them
FWD = "jit(step_fn)/jvp()/while/body/closed_call/attn/flash_fwd/pallas_call"
RECOMPUTE = ("jit(step_fn)/transpose(jvp())/while/body/closed_call/"
             "checkpoint/rematted_computation/attn/flash_fwd/pallas_call")
BWD = ("jit(step_fn)/transpose(jvp())/while/body/closed_call/checkpoint/"
       "attn/flash_dkv/pallas_call")
HEAD = "jit(step_fn)/jvp(lm_head)/while/body/closed_call/dot_general"
ADAM = "jit(step_fn)/optimizer/mul"


def _event(name: str, op_name: str) -> str:
    """An instruction's text with its metadata."""
    return (f'%{name} = bf16[8] fusion(%p), kind=kLoop, '
            f'metadata={{op_type="mul" op_name="{op_name}"}}')


def _ctx(ops):
    return types.SimpleNamespace(trace=Summary(ops))


def test_entries_unwrap_transformations():
    assert scopes.entries(HEAD)[:3] == ["step_fn", "lm_head", "while"]
    assert scopes.entries("jit(f)/transpose(jvp())/while") \
        == ["f", "", "while"]
    assert scopes.under(RECOMPUTE, scopes.REMATTED)
    assert scopes.under(HEAD, spans.LM_HEAD)
    assert not scopes.under(BWD, scopes.REMATTED)
    # the readers' kernel patterns never match a scope of the program
    assert not scopes.under(FWD, "fwd_kernel")
    assert [scopes.top_scope(n) for n in (FWD, RECOMPUTE, BWD, HEAD, ADAM,
                                          "jit(step_fn)/add")] \
        == ["attn", "rematted_computation", "attn", "lm_head", "optimizer",
            "other"]


def _step():
    """Device 0 over a 100 ms window: a forward kernel 0-30, its
    recompute 40-60, a backward kernel 60-80, the head 85-90 and a while
    40-90 holding all of the last three; device 1: the recompute 0-50."""
    return [Op(-1, WINDOW_SPAN, 0, 100 * MS, ""),
            Op(0, "fusion.1", 0, 30 * MS, _event("fusion.1", FWD)),
            Op(0, "while.2", 40 * MS, 90 * MS,
               _event("while.2", "jit(step_fn)/while")),
            Op(0, "fusion.3", 40 * MS, 60 * MS, _event("fusion.3",
                                                       RECOMPUTE)),
            Op(0, "fusion.4", 60 * MS, 80 * MS, _event("fusion.4", BWD)),
            Op(0, "fusion.5", 85 * MS, 90 * MS, _event("fusion.5", HEAD)),
            Op(1, "fusion.3", 0, 50 * MS, _event("fusion.3", RECOMPUTE))]


def test_op_names_and_own_time_by_scope():
    t = Summary(_step())
    names = scopes.op_names(t)
    assert names["fusion.3"] == RECOMPUTE
    by = scopes.by_scope(t, names)
    assert by["attn"] == pytest.approx(0.050)
    assert by["rematted_computation"] == pytest.approx(0.070)
    assert by["lm_head"] == pytest.approx(0.005)
    assert by["other"] == pytest.approx(0.0)      # the while holds others
    own = scopes.own_seconds(t, names, lambda n: "flash_" in n)
    assert own == {0: pytest.approx(0.070), 1: pytest.approx(0.050)}


def test_remat_share():
    # device 0: 20 of 80 busy ms recompute; device 1: 50 of 50
    assert REMAT_SHARE(_ctx(_step())) == pytest.approx(
        100 * (20 / 80 + 50 / 50) / 2)


def test_remat_share_zero_and_none(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "trace_dir", lambda: str(tmp_path))
    named = [o for o in _step() if "rematted" not in o.detail]
    assert REMAT_SHARE(_ctx(named)) == 0.0
    bare = [o._replace(detail="") for o in _step()]
    assert REMAT_SHARE(_ctx(bare)) is None


def _fetching():
    """Device 0 busy 20-100 of a 100 ms window; the host fetches batches
    0-30 (10 ms of it idle) and 50-60 (all of it busy)."""
    return [Op(-1, WINDOW_SPAN, 0, 100 * MS, ""),
            Op(-1, spans.DATA, 0, 30 * MS, ""),
            Op(-1, spans.DATA, 50 * MS, 60 * MS, ""),
            Op(-1, spans.DISPATCH, 30 * MS, 35 * MS, ""),
            Op(0, "fusion.1", 20 * MS, 100 * MS, "")]


def test_data_wait_share():
    assert DATA_WAIT_SHARE(_ctx(_fetching())) == pytest.approx(20.0)


def test_data_wait_share_zero_and_none(monkeypatch):
    busy = [o for o in _fetching() if o.end_ns != 30 * MS]
    assert DATA_WAIT_SHARE(_ctx(busy)) == 0.0
    no_span = [o for o in _fetching() if o.name != spans.DATA]
    assert DATA_WAIT_SHARE(_ctx(no_span)) is None
    # a program that names no data span: nothing lies under one
    monkeypatch.setattr(scopes, "program_spans", lambda: None)
    assert DATA_WAIT_SHARE(_ctx(no_span)) == 0.0


def _msg(*fields) -> bytes:
    """A protobuf message from (field number, int | bytes | str)."""
    def varint(x):
        out = b""
        while True:
            out += bytes([(x & 0x7F) | (0x80 if x > 0x7F else 0)])
            x >>= 7
            if not x:
                return out
    out = b""
    for num, val in fields:
        if isinstance(val, int):
            out += varint(num << 3) + varint(val)
        else:
            val = val.encode() if isinstance(val, str) else val
            out += varint(num << 3 | 2) + varint(len(val)) + val
    return out


def _plane(pid, name, line, events, stats=()):
    """An XPlane with one line of events [(metadata name, [(stat, str)],
    start ms, end ms)]."""
    stat_ids = {k: i for i, k in enumerate(
        sorted({k for _, st, _, _ in events for k, _ in st}), 1)}
    metas = [(4, _msg((1, m), (2, _msg(
        (1, m), (2, name_),
        *[(5, _msg((1, stat_ids[k]), (5, v))) for k, v in st]))))
        for m, (name_, st, _, _) in enumerate(events, 1)]
    evs = [(4, _msg((1, m), (2, lo * 10 ** 9), (3, (hi - lo) * 10 ** 9)))
           for m, (_, _, lo, hi) in enumerate(events, 1)]
    return _msg((1, pid), (2, name),
                (3, _msg((1, 1), (2, line), (3, 1000), *evs)), *metas,
                *[(5, _msg((1, i), (2, _msg((1, i), (2, k)))))
                  for k, i in stat_ids.items()], *stats)


#: one v5e event as the profiler records it (chip trace of
#: olmo-1b.1chip.s32k, jax 0.9.0): the instruction's text is the event's
#: name, its name stack the ``tf_op`` stat of its metadata, typed ``:``
V5E_EVENT = (
    "%flash_fwd.21 = (bf16[16,32768,128]{2,1,0:T(8,128)(2,1)}, "
    "f32[16,32768,1]{2,1,0:T(8,128)}) custom-call(s32[5]{0:T(128)S(1)} "
    "%copy-done.41, bf16[16,32768,128]{2,1,0:T(8,128)(2,1)} "
    "%maximum_bitcast_fusion.11), custom_call_target=\"tpu_custom_call\", "
    "frontend_attributes={kernel_metadata={}}",
    [("hlo_category", "custom-call"), ("deduplicated_name", "flash_fwd.20"),
     ("tf_op", RECOMPUTE + ":")])


def _recorded(tmp_path) -> str:
    """A window of 100 ms: the recorded event 10-40 ms, a fusion with no
    ``tf_op`` 50-60 ms."""
    name, stats = V5E_EVENT
    tpu = _plane(1, "/device:TPU:0", "XLA Ops",
                 [(name, stats, 10, 40),
                  ("%fusion.3 = f32[8] fusion(%p)", [], 50, 60)])
    host = _plane(2, "/host:CPU", "main", [(WINDOW_SPAN, [], 0, 100)])
    d = tmp_path / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(_msg((1, tpu), (1, host)))
    return str(tmp_path)


def test_op_name_of_the_recorded_v5e_event(tmp_path, monkeypatch):
    logdir = _recorded(tmp_path)
    assert scopes.read_xplane(scopes.newest_xplane(logdir)) \
        == {"flash_fwd.21": RECOMPUTE}
    t = Summary(trace.load(logdir))
    # the event's detail (its instruction) holds no name stack ...
    assert t.ops[0][0].name == "flash_fwd.21"
    assert "op_name" not in t.ops[0][0].detail
    # ... so the readers take it from the file beside it
    assert scopes.op_names(t, logdir) == {"flash_fwd.21": RECOMPUTE}
    monkeypatch.setattr(scopes, "trace_dir", lambda: logdir)
    assert REMAT_SHARE(types.SimpleNamespace(trace=t)) \
        == pytest.approx(100 * 30 / 40)
