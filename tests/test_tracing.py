"""The names the program writes into a profiler trace (runtime/spans.py).

A smoke-size trainer runs under ``jax.profiler.trace`` on the CPU: every
step shows as one ``train`` step annotation holding one ``train.data``
span, on the host plane.  The compiled smoke step carries the layer
scopes in its ops' ``op_name``, and JAX's own ``rematted_computation``
entry for the backward's recompute, which the benchmark's remat share
reads.
"""
import glob
import os
import re
import subprocess
import sys

import jax
import pytest

from bench.scopes import REMATTED
from repro.configs import get_reduced
from repro.core.plan import build_plan
from repro.runtime import spans
from repro.train.trainer import Trainer, TrainerConfig

STEPS = 3


@pytest.fixture(scope="module")
def trainer():
    cfg = get_reduced("olmo-1b")
    plan = build_plan(cfg, devices=jax.devices()[:1], seq_len=64,
                      global_batch=2, impl="ref")
    tr = Trainer(plan, plan.data_config(64, 2),
                 TrainerConfig(num_steps=1, log_every=1))
    assert plan.cfg.remat == "scpp"
    yield tr
    tr.guard.uninstall()


def _host_events(logdir):
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return [(ev.name, dict(ev.stats) if ev.name == spans.STEP else {})
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host")
            for line in plane.lines for ev in line.events]


def test_trainer_spans_per_step(trainer, tmp_path):
    trainer.run()                        # step 0 compiles, untraced
    trainer.start_step, trainer.tcfg.num_steps = 1, 1 + STEPS
    with jax.profiler.trace(str(tmp_path)):
        losses = trainer.run()
    assert len(losses) == STEPS
    events = _host_events(str(tmp_path))
    names = [n for n, _ in events]
    steps = [st for n, st in events if n == spans.STEP]
    assert sorted(int(st["step_num"]) for st in steps) \
        == list(range(1, 1 + STEPS))
    assert names.count(spans.DATA) == STEPS
    assert names.count(spans.DISPATCH) == STEPS
    # log_every 1: one sync per step, and the final fetch of the losses
    assert names.count(spans.SYNC) == STEPS + 1
    assert spans.CKPT not in names           # no checkpoint directory


def _under(op_name: str, scope: str) -> bool:
    """``scope`` is an entry of the name stack, bare or wrapped by a
    transformation (``jvp(lm_head)``)."""
    return re.search(rf"(^|[/(]){re.escape(scope)}([/)]|$)",
                     op_name) is not None


def test_compiled_step_carries_scopes(trainer):
    batch = trainer.data.batch(0)
    with trainer.plan.mesh:
        hlo = trainer.step_fn.lower(trainer.params, trainer.opt_state,
                                    batch).compile().as_text()
    op_names = re.findall(r'op_name="([^"]*)"', hlo)
    for scope in spans.LAYER_SCOPES + (REMATTED,):
        assert any(_under(n, scope) for n in op_names), scope
    # the recompute sits inside the backward of a layer's scope
    assert any(_under(n, REMATTED) and _under(n, spans.ATTN)
               for n in op_names)
    assert not any(_under(n, REMATTED) and _under(n, spans.OPTIMIZER)
                   for n in op_names)


@pytest.mark.dist
def test_exchange_scopes_on_four_devices():
    """``tests/_dist_checks.py::check_exchange_scopes``, in a process of
    its own (the device count is fixed at JAX's first import)."""
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(here, "..", "src"))
    script = os.path.join(here, "_dist_checks.py")
    res = subprocess.run([sys.executable, script, "exchange_scopes"],
                         capture_output=True, text=True, timeout=600,
                         env=env)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "PASS exchange_scopes" in res.stdout
