"""The controls: ways of putting something weaker in the program's place
that the comparison deciding ``correct`` has to see. Each breaks one
guarantee the configuration states, or breaks the timed path underneath.
The CPU tests run them at tiny size; ``controls_on_chip.py`` runs them at
the cells' own size. The benchmark's own runs never run them.

A control patches the verb module's ``setup`` or one method of the
program (and nothing of the harness), so the rest of a run is the real
one. Each returns the function that takes the patch off again.
"""

from __future__ import annotations

import dataclasses

import numpy as np


def _patch_setup(verb, after):
    inner = verb.setup

    def setup(cell, devices):
        ctx = inner(cell, devices)
        after(ctx)
        return ctx

    verb.setup = setup
    return lambda: setattr(verb, "setup", inner)


# -- sweep ---------------------------------------------------------------

def _rebuild_driver(ctx, cfg) -> None:
    """The sweep's driver again over ``cfg``, hook and generator kept."""
    from demi_tpu.parallel.sweep import SweepDriver

    old = ctx.driver
    ctx.driver = SweepDriver(
        ctx.app, cfg, old.program_gen, mesh=old.mesh, use_mesh=old.mesh is not None
    )
    ctx.driver.violation_hook = old.violation_hook


def sweep_invariant_at_end(verb):
    """Guarantee broken: the invariant is checked after every delivery.
    The sweep's kernels are built to check it only when a lane completes
    (the program's own cheaper path, ``invariant_interval=0``)."""
    return _patch_setup(verb, lambda ctx: _rebuild_driver(
        ctx, dataclasses.replace(ctx.cfg, invariant_interval=0)
    ))


def sweep_small_pool(verb):
    """Guarantee broken: no lane is dropped. A quarter of the pool."""
    return _patch_setup(verb, lambda ctx: _rebuild_driver(
        ctx, dataclasses.replace(ctx.cfg, pool_capacity=ctx.cfg.pool_capacity // 4)
    ))


def sweep_corrupt_codes(verb):
    """Timed path broken: every violating lane's code is altered where the
    driver hands it over."""
    def after(ctx):
        hook = ctx.driver.violation_hook
        ctx.driver.violation_hook = lambda seeds, codes: hook(seeds, np.asarray(codes) + 1)
    return _patch_setup(verb, after)


# -- dpor ----------------------------------------------------------------

def dpor_truncated_find(verb):
    """Timed path broken: the violating lane a search hands back has lost
    its last delivery, so what is lifted is not what ran."""
    from demi_tpu.device.dpor_sweep import DeviceDPOR

    inner = DeviceDPOR.explore

    def explore(self, *args, **kwargs):
        found = inner(self, *args, **kwargs)
        if found is None:
            return None
        records, trace_len = found
        return records, int(trace_len) - 1

    DeviceDPOR.explore = explore
    return lambda: setattr(DeviceDPOR, "explore", inner)


def dpor_clean_kernel(verb):
    """Guarantee broken: every violation is reported. The kernel is built
    from the protocol without the bug, so no job has a lane to lift."""
    def after(ctx):
        from demi_tpu.apps.raft import make_raft_app
        from demi_tpu.device.dpor_sweep import build_dpor_kernel

        clean = make_raft_app(ctx.cell.config["workload"]["nodes"], bug=None)
        ctx.kernel = build_dpor_kernel(clean, ctx.cfg, mesh=ctx.mesh)
    return _patch_setup(verb, after)


def dpor_drops_admissions(verb):
    """Timed path broken in the host half: every fifth fresh prescription
    the racing scan derives is refused at admission, so the search keeps
    its rate and explores another set."""
    from demi_tpu.device.dpor_sweep import DeviceDPOR

    inner = DeviceDPOR._admit
    calls = [0]

    def admit(self, presc, key, frontier):
        calls[0] += 1
        if calls[0] % 5 == 0:
            return False
        return inner(self, presc, key, frontier)

    DeviceDPOR._admit = admit
    return lambda: setattr(DeviceDPOR, "_admit", inner)


# -- minimize ------------------------------------------------------------

def minimize_clean_kernel(verb):
    """Guarantee broken: the trials are the protocol's. The replay checker
    and the gamut are given the protocol without the bug, so no trial
    reproduces and nothing is removed."""
    def after(ctx):
        from demi_tpu.apps.raft import make_raft_app

        ctx.app = make_raft_app(ctx.cell.config["workload"]["nodes"], bug=None)
    return _patch_setup(verb, after)


CONTROLS = {
    "sweep": [sweep_invariant_at_end, sweep_small_pool, sweep_corrupt_codes],
    "dpor": [dpor_truncated_find, dpor_clean_kernel, dpor_drops_admissions],
    "minimize": [minimize_clean_kernel],
}
