"""From a profiler trace to device busy time, idle share, exposed
collectives, kernel time and the breakdown.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
two lists: device operations (one ``Op`` per event on a TPU plane's
``XLA Ops`` line) and host events (``Op`` with device -1).  Everything
else works on those lists, so it can be checked on a small recorded
trace without a chip.

On a TPU an operation's event is named by its whole HLO instruction
(``%fusion.12 = bf16[...] fusion(...)``): ``load`` keeps the instruction's
name (``fusion.12``) and the text as its detail.  Events nest (a ``while``
holds the operations of its body): busy time is their union, and kernel
time, exposed collectives and the breakdown read the operations that hold
no other.  A Pallas kernel's instruction is named for where it was traced
(``checkpoint.22``), not for the kernel: ``kernel_names`` reads, from the
compiled program's text, which kernel each ``tpu_custom_call`` runs.
"""
from __future__ import annotations

import base64
import glob
import os
import re
from typing import NamedTuple

#: operations that move data between chips
COLLECTIVE = re.compile(r"all-to-all|all-gather|reduce-scatter|all-reduce|"
                        r"collective-permute|\bsend\b|\brecv\b", re.I)
#: the harness's host span around the traced window
WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"


_CUSTOM_CALL = re.compile(
    r'^\s*(?:ROOT\s+)?%?(\S+) = [^\n]*custom_call_target="tpu_custom_call"'
    r'[^\n]*"body":"([A-Za-z0-9+/=]+)"', re.M)


def _module_name(body: bytes) -> str | None:
    """The ``sym_name`` of a serialized Mosaic module: the kernel
    function's name, which Pallas gives the module."""
    from jax._src.lib.mlir import ir
    ctx = ir.Context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        attrs = ir.Module.parse(body).operation.attributes
        if "sym_name" not in attrs:
            return None
        return ir.StringAttr(attrs["sym_name"]).value


def kernel_names(hlo_text: str) -> dict[str, str]:
    """{HLO instruction name: kernel function name} for every Pallas
    kernel of a compiled program (``custom_call_config.body`` holds the
    kernel's module)."""
    out = {}
    for m in _CUSTOM_CALL.finditer(hlo_text):
        name = _module_name(base64.b64decode(m.group(2)))
        if name:
            out[m.group(1)] = name
    return out


def instruction(event_name: str) -> str:
    """``fusion.12`` from ``%fusion.12 = bf16[8] fusion(...)`` (and from
    ``fusion.12``)."""
    return event_name.partition(" = ")[0].strip().lstrip("%")


class Op(NamedTuple):
    device: int          # -1 for host events
    name: str
    start_ns: int
    end_ns: int
    detail: str          # the event's long name / op text, where given


def _detail(ev) -> str:
    for key, val in ev.stats:
        if key in ("long_name", "hlo_op", "tf_op", "kernel_details"):
            return str(val)
    return ""


def load(logdir: str) -> list[Op]:
    """Device and host events of the newest trace under ``logdir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        m = DEVICE_PLANE.search(plane.name)
        for line in plane.lines:
            if m and line.name != OPS_LINE:
                continue
            dev = int(m.group(1)) if m else -1
            if not m and not plane.name.startswith("/host"):
                continue
            out.extend(Op(dev, instruction(ev.name) if m else ev.name,
                          int(ev.start_ns), int(ev.end_ns),
                          (ev.name if " = " in ev.name else _detail(ev))
                          if m else "")
                       for ev in line.events)
    return out


def union(intervals) -> list[tuple[int, int]]:
    """Sorted, merged (start, end) intervals."""
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def minus(a, b) -> list[tuple[int, int]]:
    """Merged intervals ``a`` less merged intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def self_times(ops: list[Op]) -> list[int]:
    """Each operation's own time, in ns: its length less that of the
    operations nested in it (events of one device, which nest like a
    stack).  An operation that holds no other keeps its whole length."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i].start_ns,
                                                   -ops[i].end_ns))
    own = [o.end_ns - o.start_ns for o in ops]
    stack: list[int] = []
    for i in order:
        while stack and ops[stack[-1]].end_ns <= ops[i].start_ns:
            stack.pop()
        if stack and ops[i].end_ns <= ops[stack[-1]].end_ns:
            own[stack[-1]] -= ops[i].end_ns - ops[i].start_ns
        stack.append(i)
    return own


class Summary:
    """The trace of one window, reduced per device."""

    def __init__(self, ops: list[Op], devices=None, kernels=None):
        """``kernels``: {instruction name: kernel name}, from
        ``kernel_names``."""
        self.kernels = dict(kernels or {})
        spans = [o for o in ops if o.device < 0 and o.name == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"no host span {WINDOW_SPAN!r} in the trace")
        self.lo, self.hi = spans[-1].start_ns, spans[-1].end_ns
        self.host = [o for o in ops if o.device < 0
                     and o.end_ns > self.lo and o.start_ns < self.hi]
        dev_ops = [o for o in ops if o.device >= 0
                   and o.end_ns > self.lo and o.start_ns < self.hi]
        self.devices = sorted(devices if devices is not None
                              else {o.device for o in dev_ops})
        self.ops = {d: [o for o in dev_ops if o.device == d]
                    for d in self.devices}
        self.own = {d: self_times(self.ops[d]) for d in self.devices}
        #: per device, the operations that hold no other
        self.leaves = {d: [o for o, own in zip(self.ops[d], self.own[d])
                           if own == o.end_ns - o.start_ns]
                       for d in self.devices}

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def _iv(self, d: int, keep, ops=None) -> list[tuple[int, int]]:
        return clip(union((o.start_ns, o.end_ns)
                          for o in (self.leaves[d] if ops is None else ops)
                          if keep(o)), self.lo, self.hi)

    def busy(self, d: int) -> list[tuple[int, int]]:
        return self._iv(d, lambda o: True, self.ops[d])

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over devices."""
        if not self.devices:
            return 0.0
        return sum(length(self.busy(d)) for d in self.devices) \
            / len(self.devices) / 1e9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def exposed_collective_s(self, d: int) -> float:
        """Seconds in which a collective ran on device ``d`` and no other
        operation (that holds no other) did."""
        comm = self._iv(d, lambda o: bool(COLLECTIVE.search(o.name)))
        comp = self._iv(d, lambda o: not COLLECTIVE.search(o.name))
        return length(minus(comm, comp)) / 1e9

    def op_seconds(self, pattern: re.Pattern) -> float:
        """Summed device time of the operations (that hold no other) whose
        name, detail or kernel matches, over all devices."""
        return sum(o.end_ns - o.start_ns for d in self.devices
                   for o in self.leaves[d]
                   if pattern.search(o.name) or pattern.search(o.detail)
                   or pattern.search(self.kernels.get(o.name, ""))) / 1e9

    def top_ops(self, n: int = 10) -> list[list]:
        """[name, seconds]: each operation's own time (less that of the
        operations nested in it) by name, summed over devices, numbered
        suffixes (``fusion.12``) folded, a Pallas kernel's instruction
        named by its kernel."""
        acc: dict[str, int] = {}
        for d in self.devices:
            for o, own in zip(self.ops[d], self.own[d]):
                key = self.kernels.get(o.name) or re.sub(r"[.:]\d+$", "",
                                                          o.name)
                acc[key] = acc.get(key, 0) + own
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9] for k, v in top]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """[label, seconds]: the longest idle gaps of the first device,
        each labelled by the shortest host event that covers most of it."""
        if not self.devices:
            return []
        d = self.devices[0]
        gaps = minus([(self.lo, self.hi)], self.busy(d))
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
        out = []
        for s, e in gaps:
            label, best = "no host event", None
            for h in self.host:
                if h.name == WINDOW_SPAN:
                    continue
                cover = min(e, h.end_ns) - max(s, h.start_ns)
                if cover * 2 >= e - s and (
                        best is None or h.end_ns - h.start_ns < best):
                    label, best = h.name, h.end_ns - h.start_ns
            out.append([label, (e - s) / 1e9])
        return out
