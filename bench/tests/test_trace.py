"""The reduction from trace events to busy time, idle share, exposed
collectives and kernel time."""
import base64
import re

import pytest

from bench.trace import (WINDOW_SPAN, Op, Summary, clip, instruction,
                         kernel_names, length, minus, self_times, union)

MS = 1_000_000


def _ops():
    """Two devices over a 100 ms window.  Device 0: a kernel 10-40, a
    fusion 30-50 (overlapping it), an all-gather 45-70 (exposed 50-70),
    a collective-permute 80-90 under a fusion 75-95.  Device 1: one
    all-to-all 0-20, alone."""
    return [
        Op(-1, WINDOW_SPAN, 0, 100 * MS, ""),
        Op(-1, "PjitFunction(step_fn)", 60 * MS, 72 * MS, ""),
        Op(0, "custom-call.3", 10 * MS, 40 * MS, "_fwd_kernel"),
        Op(0, "fusion.12", 30 * MS, 50 * MS, ""),
        Op(0, "all-gather-start.1", 45 * MS, 70 * MS, ""),
        Op(0, "fusion.13", 75 * MS, 95 * MS, ""),
        Op(0, "collective-permute-done.2", 80 * MS, 98 * MS, ""),
        Op(1, "all-to-all.4", 0, 20 * MS, ""),
        Op(1, "custom-call.9", 95 * MS, 120 * MS, "_dq_kernel"),
    ]


def test_interval_algebra():
    assert union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert minus([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert minus([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert clip([(-5, 5), (90, 120)], 0, 100) == [(0, 5), (90, 100)]
    assert length([(0, 3), (5, 8)]) == 6


def test_busy_idle_and_exposed_collectives():
    s = Summary(_ops())
    assert s.window_s == pytest.approx(0.1)
    assert s.devices == [0, 1]
    # device 0 busy 10-70 and 75-98 = 83 ms; device 1 0-20, 95-100 = 25 ms
    assert s.busy_s() == pytest.approx((0.083 + 0.025) / 2)
    assert s.idle_share() == pytest.approx(1 - 0.054 / 0.1)
    assert s.exposed_collective_s(0) == pytest.approx(0.023)
    assert s.exposed_collective_s(1) == pytest.approx(0.020)


def test_kernel_time_by_name_and_breakdown():
    s = Summary(_ops())
    assert s.op_seconds(re.compile("fwd_kernel")) == pytest.approx(0.030)
    # the dq kernel is cut by nothing here: events are summed whole
    assert s.op_seconds(re.compile("dq_kernel")) == pytest.approx(0.025)
    top = dict(s.top_ops())
    assert top["fusion"] == pytest.approx(0.040)
    gaps = s.idle_gaps()
    assert gaps[0][1] == pytest.approx(0.010)       # device 0: 0-10
    assert gaps[0][0] == "no host event"
    assert [g for g in gaps if g[1] == pytest.approx(0.005)][0][0] \
        in ("PjitFunction(step_fn)", "no host event")


def _custom_call(name, body: bytes, root=False):
    b64 = base64.b64encode(body).decode()
    return (f'  {"ROOT " if root else ""}%{name} = bf16[16,1024,128] '
            f'custom-call(%a, %b), custom_call_target="tpu_custom_call", '
            f'backend_config={{"custom_call_config":{{"body":"{b64}",'
            f'"needs_layout_passes":true}}}}')


def test_kernel_names_from_compiled_text():
    def module(name):
        # a kernel's module, as Pallas names it for the kernel function
        return (f"module @{name} attributes {{stable_mosaic.version = 9 : "
                f"i64}} {{}}").encode()

    hlo = "\n".join([
        "  %fusion.3 = f32[8] fusion(%p), kind=kLoop",
        _custom_call("jvp__.1", module("_fwd_kernel")),
        _custom_call("transpose_jvp___.2", module("_dq_kernel")),
        _custom_call("transpose_jvp___.3", module("_dkv_kernel"), root=True),
        _custom_call("custom-call.9", b"module {}"),
    ])
    names = kernel_names(hlo)
    assert names == {"jvp__.1": "_fwd_kernel",
                     "transpose_jvp___.2": "_dq_kernel",
                     "transpose_jvp___.3": "_dkv_kernel"}
    # a trace event carries the instruction's name; the map finds its kernel
    s = Summary([Op(-1, WINDOW_SPAN, 0, 100 * MS, ""),
                 Op(0, "jvp__.1", 0, 30 * MS, ""),
                 Op(0, "transpose_jvp___.2", 40 * MS, 50 * MS, ""),
                 Op(0, "transpose_jvp___.3", 50 * MS, 70 * MS, "")],
                kernels=names)
    assert s.op_seconds(re.compile("fwd_kernel")) == pytest.approx(0.030)
    assert s.op_seconds(re.compile("dq_kernel|dkv_kernel")) \
        == pytest.approx(0.030)
    assert dict(s.top_ops())["_fwd_kernel"] == pytest.approx(0.030)


def test_nested_events_instruction_names_and_own_time():
    assert instruction("%while.21 = (s32[], bf16[8]) while(%t)") == "while.21"
    assert instruction("fusion.4") == "fusion.4"
    # device 0: a while 0-60 holding a fusion 10-20 and an all-gather 30-50
    # (exposed: the while around it is no compute of its own); a fusion
    # 70-80 outside it
    ops = [Op(-1, WINDOW_SPAN, 0, 100 * MS, ""),
           Op(0, "while.2", 0, 60 * MS, ""),
           Op(0, "fusion.1", 10 * MS, 20 * MS, ""),
           Op(0, "all-gather.3", 30 * MS, 50 * MS, ""),
           Op(0, "fusion.4", 70 * MS, 80 * MS, "")]
    assert self_times(ops[1:]) == [30 * MS, 10 * MS, 20 * MS, 10 * MS]
    s = Summary(ops)
    assert s.busy_s() == pytest.approx(0.070)
    assert [o.name for o in s.leaves[0]] == ["fusion.1", "all-gather.3",
                                             "fusion.4"]
    assert s.exposed_collective_s(0) == pytest.approx(0.020)
    top = dict(s.top_ops())
    assert top["while"] == pytest.approx(0.030)
    assert top["fusion"] == pytest.approx(0.020)
