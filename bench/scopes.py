"""Per device operation of a traced window, its HLO ``op_name``; own
device time by name scope.

An ``op_name`` is the name stack an operation was traced under
(``jit(step_fn)/transpose(jvp())/while/body/closed_call/checkpoint/
rematted_computation/attn/dot_general``): the program's
``jax.named_scope`` entries (``runtime/spans.py``), JAX's
transformations wrapped around them (``jvp(lm_head)``), and JAX's own
entries, among them ``rematted_computation``, under which the backward
recomputes what the remat policy did not save.

``op_names`` reads it, per device operation (by instruction name), from
the first place that holds it: the detail ``bench.trace.load`` kept for
the event (``metadata={op_name="..."}`` in an instruction's text); else
the ``tf_op`` stat of the event's metadata in the window's
``.xplane.pb`` (under the checkout at ``bench.harness.TRACE_DIR``), which
is where a TPU v5e trace holds it and which ``jax.profiler.ProfileData``
does not expose.

    python -m bench.scopes [<logdir>]    # default: .bench_trace

prints own device seconds per top-level scope of a traced window.
"""
from __future__ import annotations

import functools
import glob
import os
import re
import sys

from bench.trace import (DEVICE_PLANE, Summary, clip, instruction, length,
                         load, union)

#: JAX's name-stack entry for the backward's recompute
REMATTED = "rematted_computation"
OTHER = "other"

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_IN_TEXT = re.compile(r'op_name="([^"]*)"')
_WRAP = re.compile(r"^(?:[\w.-]+\()*([^()]*)\)*$")
#: the stat of a TPU op's event metadata that holds its name stack
TF_OP = "tf_op"


@functools.cache
def program_spans():
    """The program's ``repro.runtime.spans`` (the names it gives its
    trace), or None for a program from before it named them."""
    try:
        from repro.runtime import spans
    except ImportError:
        return None
    return spans


def layer_scopes() -> tuple[str, ...]:
    """The program's step-level scopes (none where it names none)."""
    spans = program_spans()
    return spans.LAYER_SCOPES if spans else ()


def entries(op_name: str) -> list[str]:
    """The name stack's entries, each without the transformations
    wrapped around it: ``jit(f)/jvp(lm_head)/mul`` -> ``[f, lm_head,
    mul]`` (an entry that is only transformations, ``transpose(jvp())``,
    gives ``""``)."""
    return [_WRAP.sub(r"\1", e) for e in op_name.split("/")]


def under(op_name: str, scope: str) -> bool:
    return scope in entries(op_name)


def top_scope(op_name: str) -> str:
    """``rematted_computation`` for the backward's recompute, else the
    outermost of the program's step-level scopes, else ``other``."""
    found = entries(op_name)
    if REMATTED in found:
        return REMATTED
    scopes = layer_scopes()
    for e in found:
        if e in scopes:
            return e
    return OTHER


# -- the .xplane.pb, read as protobuf wire format (nothing but Python) ----

def _varint(b, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b):
    """(field number, value) of one message; a length-delimited value is a
    memoryview."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def _str(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _entry(b) -> tuple[int, memoryview]:
    """A protobuf map entry: (key, value)."""
    key, val = 0, memoryview(b"")
    for f, v in _fields(b):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _tf_ops(plane) -> dict[str, str]:
    """{instruction: tf_op} of one XPlane's event metadata (XPlane field 4:
    name 2, stats 5; field 5: stat metadata, name 2; XStat: metadata id
    1, string 5)."""
    metas, stat_names = [], {}
    for f, v in _fields(plane):
        if f == 4:
            metas.append(_entry(v)[1])
        elif f == 5:
            key, meta = _entry(v)
            stat_names[key] = next((_str(w) for g, w in _fields(meta)
                                    if g == 2), "")
    out = {}
    for meta in metas:
        name, op = "", ""
        for g, w in _fields(meta):
            if g == 2:
                name = _str(w)
            elif g == 5:
                st = dict(_fields(w))
                if stat_names.get(st.get(1)) == TF_OP and 5 in st:
                    op = _str(st[5])
        if name and op:
            out[instruction(name)] = op
    return out


def read_xplane(path: str) -> dict[str, str]:
    """{instruction: op_name} of a ``.xplane.pb``'s TPU planes, from the
    ``tf_op`` stat of their event metadata (``<op_name>:<op_type>``; JAX
    leaves the type empty)."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    out = {}
    for fnum, plane in _fields(data):
        if fnum != 1:
            continue
        name = next((_str(v) for f, v in _fields(plane) if f == 2), "")
        if DEVICE_PLANE.search(name):
            out.update({k: _op_name(v) for k, v in _tf_ops(plane).items()})
    return out


def _op_name(tf_op: str) -> str:
    """``<op_name>:<op_type>`` -> ``<op_name>``."""
    head, colon, tail = tf_op.rpartition(":")
    return head if colon and "/" not in tail else tf_op


def newest_xplane(logdir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    return paths[-1] if paths else None


def trace_dir() -> str:
    from bench.harness import TRACE_DIR
    return os.path.join(ROOT, TRACE_DIR)


def op_names(t: Summary, logdir: str | None = None) -> dict[str, str]:
    """{instruction: op_name} for the window's device operations: from the
    events' details where they hold it, else from the trace file."""
    out = {o.name: m.group(1) for d in t.devices for o in t.ops[d]
           if (m := _IN_TEXT.search(o.detail))}
    if out:
        return out
    path = newest_xplane(logdir or trace_dir())
    return read_xplane(path) if path else {}


def own_seconds(t: Summary, names: dict[str, str], keep) -> dict[int, float]:
    """Per device, the window's own device time (of the operations that
    hold no other) whose ``op_name`` ``keep`` accepts."""
    return {d: length(clip(union((o.start_ns, o.end_ns)
                                 for o in t.leaves[d]
                                 if keep(names.get(o.name, ""))),
                           t.lo, t.hi)) / 1e9
            for d in t.devices}


def busy_share(t: Summary, names: dict[str, str], keep) -> float:
    """The share of each device's busy time that ``own_seconds`` gives,
    averaged over devices."""
    own = own_seconds(t, names, keep)
    shares = [own[d] / (length(t.busy(d)) / 1e9) for d in t.devices
              if length(t.busy(d))]
    return sum(shares) / len(shares) if shares else 0.0


def by_scope(t: Summary, names: dict[str, str]) -> dict[str, float]:
    """Own device seconds, summed over devices, per ``top_scope``."""
    out = {s: 0.0 for s in layer_scopes() + (REMATTED, OTHER)}
    for d in t.devices:
        for o in t.leaves[d]:
            s = top_scope(names.get(o.name, ""))
            lo, hi = max(o.start_ns, t.lo), min(o.end_ns, t.hi)
            if hi > lo:
                out[s] += (hi - lo) / 1e9
    return out


def main(logdir: str | None = None) -> None:
    logdir = logdir or trace_dir()
    t = Summary(load(logdir))
    names = op_names(t, logdir)
    spans = program_spans()
    steps = sum(1 for h in t.host
                if spans and h.name == spans.STEP
                and t.lo <= h.start_ns and h.end_ns <= t.hi) or 1
    by = by_scope(t, names)
    total = sum(by.values()) or 1.0
    print(f"window {t.window_s:.3f} s, {steps} step(s), {len(t.devices)} "
          f"device(s), {len(names)} ops named")
    print(f"{'scope':<22}{'s/step':>10}{'share':>9}")
    for scope, s in by.items():
        print(f"{scope:<22}{s / steps:>10.4f}{100 * s / total:>8.2f}%")


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(ROOT, "src"))
    main(*sys.argv[1:])
