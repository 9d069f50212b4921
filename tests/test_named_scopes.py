"""Every stage of a ZEUS solve carries its `jax.named_scope` into the
compiled program, so a profiler trace groups the chip's ops by stage through
their `op_name` (DESIGN.md §19). Each path is compiled at a tiny size on the
CPU; nothing runs."""
import os
import re
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.core import BFGSOptions, PSOOptions, ZeusOptions
from repro.core.meanfield import MeanFieldPSOOptions
from repro.core.objectives import get_objective
from repro.core.zeus import zeus_jit
from repro.kernels import ops as kernel_ops

STAGES_OF_SOLVE = {"zeus.phase1", "zeus.phase2", "zeus.finale"}
STAGED = {"zeus.phase2.ladder", "zeus.phase2.gradient", "zeus.phase2.update",
          "zeus.phase2.accept"}


def _stage_scopes(sweep_mode):
    if sweep_mode == "megakernel" and kernel_ops.pallas_enabled():
        return {"zeus.phase2.fused_sweep", "zeus.phase2.accept"}
    return STAGED  # without Pallas the megakernel step is the staged step


def scopes_in(text):
    """The `zeus.*` scopes named in any op_name of a program's text (under
    a transform a scope reads `vmap(zeus.phase2.update)`)."""
    return {s for name in re.findall(r'op_name="([^"]*)"', text)
            for s in re.findall(r"zeus(?:\.\w+)+", name)}


@pytest.mark.parametrize("phase1", ["pso", "meanfield"])
@pytest.mark.parametrize("sweep_mode", ["per_lane", "batched", "megakernel"])
def test_solve_carries_its_scopes(sweep_mode, phase1):
    n = 32
    opts = ZeusOptions(
        pso=PSOOptions(n_particles=n, iter_pso=2),
        meanfield=MeanFieldPSOOptions(n_particles=n, iter_pso=2),
        bfgs=BFGSOptions(iter_bfgs=3), phase1=phase1, sweep_mode=sweep_mode,
        lane_chunk=16)
    solve = zeus_jit(get_objective("rastrigin").fn, 3, -5.12, 5.12, opts)
    text = solve.lower(jax.random.key(0)).compile().as_text()
    found = scopes_in(text)
    assert STAGES_OF_SOLVE | _stage_scopes(sweep_mode) <= found
    # no scope outside the table of stages
    assert found <= STAGES_OF_SOLVE | STAGED | {"zeus.phase2.fused_sweep"}


def test_distributed_collectives_carry_their_stage():
    """On a (4,) mesh every collective sits in its stage: the swarm's
    pmin/psum in phase 1, the stop count's psum in zeus.phase2.stop, the
    result's reductions in the finale. A subprocess, for the host devices."""
    code = textwrap.dedent("""
        import jax
        from repro.core import BFGSOptions, PSOOptions, ZeusOptions
        from repro.core.distributed import distributed_zeus
        from repro.core.objectives import get_objective
        from repro.sharding import make_mesh
        opts = ZeusOptions(pso=PSOOptions(n_particles=64, iter_pso=2),
                           bfgs=BFGSOptions(iter_bfgs=3),
                           sweep_mode="batched")
        obj = get_objective("rastrigin")
        fn = distributed_zeus(obj.fn, 3, obj.lower, obj.upper, opts,
                              make_mesh((4,), ("data",)))
        print(jax.jit(fn).lower(jax.random.key(0)).compile().as_text())
        """)
    env = dict(os.environ, PYTHONPATH=os.path.join(
        os.path.dirname(__file__), "..", "src"),
        XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    collectives = [line for line in p.stdout.splitlines()
                   if re.search(r"\sall-reduce(-start)?\(", line)]
    # each one's innermost scope
    stages = {re.findall(r"zeus(?:\.\w+)+", re.search(
        r'op_name="([^"]*)"', line).group(1))[-1] for line in collectives}
    assert stages == {"zeus.phase1", "zeus.phase2.stop", "zeus.finale"}
