"""Jacobi sweeps through ``repro.core.halo.jacobi_step``, as the paper's
§5.4 solver (after NVIDIA's multi-gpu-programming-models ``jacobi``) runs
them on one device.

The sweep is ``jacobi_step(multipath=True, use_kernel=True)`` inside a
``shard_map`` over the session's mesh, jitted: one sweep per dispatch, and a
wait on every iteration, as a solver that checks convergence each iteration
does. After ``iter_max`` iterations the solve starts again from the seeded
grid, as a sequence of solves of the configuration's length would. A sample
of iterations, drawn from the seed, keeps its input and output; after the
window each is compared with the ``jax.numpy`` sweep of the same input.

Traffic parameters (``params`` of the workload file): ``cols`` (the width),
``sample`` (iterations compared) and ``warmup`` (sweeps before the window).
The configuration gives ``rows``, ``dtype`` and ``iter_max``.
"""

from __future__ import annotations

import numpy as np

import harness
import reference

END_TO_END = {"step_ms": "ms"}


class Run:
    def __init__(self, s):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from repro.comm import CommConfig, CommSession
        from repro.core.halo import jacobi_step

        cfg, p = s.cell.config, s.cell.params
        if cfg["dtype"] != "float32":
            raise harness.BenchError("jacobi sweeps a float32 grid")
        self.rows, self.cols = int(cfg["rows"]), int(p["cols"])
        self.iter_max = int(cfg["iter_max"])
        session = CommSession(
            CommConfig(), mesh=Mesh(np.array(s.devices), ("dev",)))
        ax, mesh = session.axis_name, session.mesh
        if s.control:
            # The reference in the program's place, computed in bfloat16.
            def sweep(x):
                return reference.jacobi_step_ref(x[0], jnp.bfloat16)[None]
            self._sweep = jax.jit(sweep)
        else:
            self._sweep = jax.jit(jax.shard_map(
                lambda x: jacobi_step(x[0], ax, multipath=True,
                                      use_kernel=True)[None],
                mesh=mesh, in_specs=P(ax), out_specs=P(ax),
                check_vma=False))
        self.u0 = reference.random_f32(
            s.seed, (len(s.devices), self.rows, self.cols),
            NamedSharding(mesh, P(ax)))
        self.u = self.u0
        self.samples = harness.Reservoir(int(p["sample"]), s.seed)
        self.n = 0
        for _ in range(int(p["warmup"])):
            jax.block_until_ready(self._sweep(self.u0))

    def start_window(self, traced: bool) -> None:
        pass

    def call(self):
        """One sweep; a new solve starts every ``iter_max`` sweeps."""
        self._in = self.u0 if self.n % self.iter_max == 0 else self.u
        return self._sweep(self._in)

    def done(self, out) -> None:
        self.samples.offer((self._in, out))
        self.u = out
        self.n += 1

    def end_to_end(self, window_s: float, lat_ns: list) -> dict[str, float]:
        """The window over the iterations completed in it."""
        return {"step_ms": window_s / len(lat_ns) * 1e3}

    def layer_inputs(self) -> dict:
        return {"rows": self.rows, "cols": self.cols}

    def release(self) -> None:
        """Free the program's state; the sampled iterations stay."""
        for attr in ("_sweep", "u0", "u", "_in"):
            self.__dict__.pop(attr, None)

    def check(self) -> tuple[list[harness.Check], int]:
        import jax
        ref = jax.jit(reference.jacobi_step_ref)
        worst, failed = 0.0, 0
        for _, (u, out) in self.samples.items:
            gap = reference.relative_gap(out[0], ref(u[0]))
            worst, failed = max(worst, gap), failed + (gap > LIMIT)
        return [harness.Check("relative_gap", worst, LIMIT)], failed


#: Largest max |sweep - reference| / max |reference| of a sound sweep; set
#: from the readings in PERF.md.
LIMIT = 1e-4


def setup(s) -> Run:
    return Run(s)
