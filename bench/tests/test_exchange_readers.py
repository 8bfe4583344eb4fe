"""The exchange's readers, ``a2a_exposed_share``, ``ring_exposed_share``
and ``collective_exposed_share`` (``bench/exchange.py``), on small written
traces."""
import types

import pytest

from bench.manifest import Manifest
from bench.trace import WINDOW_SPAN, Op, Summary
from conftest import REPO

MS = 1_000_000
MAN = Manifest(REPO)
A2A = MAN.reader("a2a_exposed_share")
RING = MAN.reader("ring_exposed_share")
ALL = MAN.reader("collective_exposed_share")

#: name stacks of the compiled step, as JAX writes them
A2A_OP = ("jit(step_fn)/jvp()/while/body/closed_call/attn/shard_map/"
          "ulysses_a2a/all_to_all")
A2A_BWD = ("jit(step_fn)/transpose(jvp())/while/body/closed_call/attn/"
           "shard_map/transpose(jvp(ulysses_a2a))/all_to_all")
RING_OP = ("jit(step_fn)/jvp()/while/body/closed_call/attn/shard_map/"
           "ring/ppermute")
KERNEL = ("jit(step_fn)/jvp()/while/body/closed_call/attn/shard_map/ring/"
          "flash_fwd/pallas_call")
ZERO = "jit(step_fn)/optimizer/all-gather"


def _op(dev, name, lo, hi, op_name, opcode="fusion"):
    """An event as a TPU trace gives it: the instruction's name, and its
    text, which holds the opcode and the ``op_name``."""
    return Op(dev, name, lo * MS, hi * MS,
              f'%{name} = bf16[8]{{0:T(8)}} {opcode}(%p.1), '
              f'metadata={{op_type="x" op_name="{op_name}"}}')


def _a2a(dev, lo, hi, op_name=A2A_OP):
    """JAX names the instruction for its primitive: only the opcode says
    that it is a collective."""
    return _op(dev, "all_to_all.1", lo, hi, op_name, "all-to-all")


def _ring(dev, lo, hi):
    return _op(dev, "collective-permute-done.3", lo, hi, RING_OP,
               "collective-permute-done")


def _read(reader, *ops):
    t = Summary([Op(-1, WINDOW_SPAN, 0, 100 * MS, "")] + list(ops))
    return reader(types.SimpleNamespace(trace=t))


def test_collective_under_kernels_counts_zero():
    """10-20, overlapped by kernels 5-15 and 15-25 (events that overlap
    in part; one held whole in another would be its child)."""
    ops = (_a2a(0, 10, 20), _op(0, "fusion.2", 5, 15, KERNEL),
           _op(0, "fusion.5", 15, 25, KERNEL))
    assert _read(A2A, *ops) == 0.0


@pytest.mark.parametrize("reader,event", [
    (A2A, lambda d, lo, hi: _a2a(d, lo, hi, A2A_BWD)),
    (RING, _ring),
])
def test_exposed_collective_counts_its_time(reader, event):
    """40-60 on device 0, half under a kernel (50-70): 10 ms exposed; on
    device 1 the whole 20 ms, and the fullest device is what counts."""
    ops = (event(0, 40, 60), _op(0, "fusion.2", 50, 70, KERNEL),
           event(1, 40, 60))
    assert _read(reader, *ops[:2]) == pytest.approx(10.0)
    assert _read(reader, *ops) == pytest.approx(20.0)


def test_collective_under_the_other_scope_is_not_counted():
    ops = (_a2a(0, 10, 20), _op(0, "fusion.2", 5, 15, KERNEL),
           _op(0, "fusion.5", 15, 25, KERNEL), _ring(0, 40, 60))
    assert _read(A2A, *ops) == 0.0
    assert _read(RING, *ops) == pytest.approx(20.0)


def test_an_operation_that_reads_a_collective_is_no_collective():
    """A fusion whose operand is an all-gather's result (``%all-gather``
    in its text) is compute, and hides the ring under it."""
    ops = (_ring(0, 40, 60),
           Op(0, "fusion.7", 35 * MS, 50 * MS,
              "%fusion.7 = bf16[8]{0} fusion(%all-gather.2), kind=kLoop"))
    assert _read(RING, *ops) == pytest.approx(10.0)


def test_none_where_no_scoped_collective_ran():
    """ZeRO's all-gather is a collective under neither scope."""
    ops = (_op(0, "fusion.2", 5, 25, KERNEL),
           _op(0, "all-gather-start.4", 30, 35, ZERO, "all-gather-start"))
    assert _read(A2A, *ops) is None
    assert _read(RING, *ops) is None
    assert _read(ALL, *ops) == pytest.approx(5.0)


def test_async_collective_fusion_is_a_collective():
    """The TPU compiler's ``async-collective-done`` (opcode ``fusion``, no
    scope: ZeRO's gathers) is a collective: it hides no ring pass under it,
    and counts among all the collectives."""
    ops = (_ring(0, 40, 60),
           _op(0, "async-collective-done.11", 30, 50, "", "fusion"),
           _op(0, "fusion.2", 5, 25, KERNEL))
    assert _read(RING, *ops) == pytest.approx(20.0)
    assert _read(ALL, *ops) == pytest.approx(30.0)
    assert _read(A2A, *ops) is None
