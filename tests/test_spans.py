"""Names on the profiler's clock: the ``jacobi.*`` scopes of the sweep, the
``comm.*`` spans of the send path, and the stage timings they fill.

* ``jacobi_step`` carries each scope in its ops' metadata, on the kernel
  path and the ``jnp`` path, and compiles to the same program with the
  metadata stripped as it does without the scopes,
* a 32 B send and an exchange on four CPU devices, under
  ``jax.profiler.trace``, give ``comm.send`` (``comm.exchange``) holding
  ``comm.resolve`` < ``comm.place`` < ``comm.stage`` < ``comm.launch`` <
  ``comm.extract`` on one host line; a miss adds the miss-only spans inside
  ``comm.resolve``, a hit does not,
* with telemetry on, ``StageTimings`` fills every field it filled before.
"""

import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.comm import CommConfig, CommSession
from repro.comm.telemetry import STAGES, StageTimings, span
from repro.core import Topology
from repro.core.halo import jacobi_step, make_captured_jacobi_step

GLUE = ("jacobi.halo", "jacobi.edges")
MISS_ONLY = ["comm.plan", "comm.lower", "comm.schedule", "comm.compile"]
PER_SEND = ["comm.resolve", "comm.place", "comm.stage", "comm.launch",
            "comm.execute", "comm.extract"]


def _mesh(n=4):
    return Mesh(np.array(jax.devices()[:n]), ("dev",))


def _sweep(use_kernel):
    mesh = _mesh()
    f = jax.jit(jax.shard_map(
        lambda x: jacobi_step(x[0], "dev", multipath=True,
                              use_kernel=use_kernel)[None],
        mesh=mesh, in_specs=P("dev"), out_specs=P("dev"), check_vma=False))
    return f.lower(jax.ShapeDtypeStruct((4, 8, 1024), jnp.float32))


def _stripped(compiled_text):
    """The optimized HLO without op metadata or the source-location
    tables after it."""
    text = compiled_text.split("\nFileNames\n", 1)[0]
    return re.sub(r",? metadata=\{[^}]*\}", "", text)


@pytest.mark.parametrize("use_kernel", [True, False],
                         ids=["kernel", "jnp"])
def test_jacobi_scopes_are_metadata_only(use_kernel, monkeypatch):
    lowered = _sweep(use_kernel)
    # The kernel reads the block in place: no halo-extended block is built.
    scopes = GLUE + (() if use_kernel else ("jacobi.extend",)) + (
        "jacobi.stencil",)
    found = set(re.findall(r"jacobi\.[a-z]+",
                           lowered.as_text(debug_info=True)))
    assert found == set(scopes)
    compiled = _stripped(lowered.compile().as_text())
    assert "jacobi." not in compiled
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert compiled == _stripped(_sweep(use_kernel).compile().as_text())


def _comm_spans(tmp_path):
    """The ``comm.*`` spans of the host line that holds them, in time
    order, as (name, start, end, args)."""
    from jax.profiler import ProfileData

    (path,) = tmp_path.rglob("*.xplane.pb")
    lines = [ln for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:") for ln in plane.lines
             if any(e.name.startswith("comm.") for e in ln.events)]
    assert len(lines) == 1
    return sorted(((e.name, e.start_ns, e.end_ns, dict(e.stats))
                   for e in lines[0].events if e.name.startswith("comm.")),
                  key=lambda s: (s[1], -s[2]))


def _children(spans, outer):
    _, s, e, _ = outer
    return [x for x in spans if s <= x[1] and x[2] <= e and x is not outer]


@pytest.mark.parametrize("call", ["send", "exchange"])
def test_send_path_spans_nest_in_order(call, tmp_path):
    session = CommSession(CommConfig(), mesh=_mesh(),
                          topology=Topology.full_mesh(4, with_host=False))
    x = jnp.arange(8, dtype=jnp.float32)          # 32 B

    def once():
        if call == "send":
            return session.send(x, 0, 3)
        return session.exchange([(x, 0, 3), (x, 3, 0)])

    jax.block_until_ready(once())                 # compile outside the trace
    session.engine._fastpath.clear()              # the traced first call misses
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(once())
        jax.block_until_ready(once())
    spans = _comm_spans(tmp_path)
    outers = [s for s in spans if s[0] == f"comm.{call}"]
    assert len(outers) == 2
    for outer, hit in zip(outers, (0, 1)):
        inner = _children(spans, outer)
        names = [s[0] for s in inner]
        (resolve,) = [s for s in inner if s[0] == "comm.resolve"]
        assert resolve[3] == {"hit": hit}
        in_resolve = [s[0] for s in _children(inner, resolve)]
        # The program is in the plan cache already: a miss plans, lowers
        # and schedules, and builds nothing.
        assert in_resolve == ([] if hit else MISS_ONLY[:3])
        top = [n for n in names if n not in in_resolve]
        assert top == PER_SEND
        ends = [s[2] for s in inner if s[0] in PER_SEND]
        starts = [s[1] for s in inner if s[0] in PER_SEND]
        assert all(e <= s for e, s in zip(ends, starts[1:]))


def test_a_cold_miss_spans_the_compile(tmp_path):
    session = CommSession(CommConfig(), mesh=_mesh(),
                          topology=Topology.full_mesh(4, with_host=False))
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(session.send(jnp.ones(8), 0, 1))
    spans = _comm_spans(tmp_path)
    (resolve,) = [s for s in spans if s[0] == "comm.resolve"]
    assert [s[0] for s in _children(spans, resolve)] == MISS_ONLY


def _telemetry_session():
    return CommSession(CommConfig(telemetry=True), mesh=_mesh(),
                       topology=Topology.full_mesh(4, with_host=False))


def test_stage_timings_fill_every_field():
    session = _telemetry_session()
    x = jnp.arange(8, dtype=jnp.float32)
    for _ in range(2):
        jax.block_until_ready(session.send(x, 0, 3))
    jax.block_until_ready(session.send(x, 0, 3, block=False))
    miss, hit, unblocked = (s.stages for s in session.telemetry.samples())
    assert all(v > 0 for v in miss.as_dict().values()), miss
    setup = ("plan", "lower", "schedule", "compile")
    assert all((v > 0) != (k in setup) for k, v in hit.as_dict().items())
    assert unblocked.execute_ns == 0 and unblocked.launch_ns > 0
    assert session.engine.staging_ns >= (miss.staging_ns + hit.staging_ns
                                         + unblocked.staging_ns)


def test_captured_step_timings_and_spans(tmp_path):
    session = _telemetry_session()
    step = make_captured_jacobi_step(session, 8, 256)
    u = jnp.ones((4, 8, 256), jnp.float32)
    jax.block_until_ready(step(u))
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(step(u))
    miss, hit = (s.stages for s in session.telemetry.samples())
    # A captured step has no planner stage of its own (lower_step plans).
    assert miss.plan_ns == 0
    assert all(getattr(miss, f"{k}_ns") > 0 for k in STAGES if k != "plan")
    assert hit.lower_ns == hit.compile_ns == 0 and hit.execute_ns > 0
    spans = _comm_spans(tmp_path)
    (outer,) = [s for s in spans if s[0] == "comm.step"]
    assert [s[0] for s in _children(spans, outer)] == [
        "comm.resolve", "comm.place", "comm.launch", "comm.execute"]


def test_span_adds_to_its_field_and_costs_nothing_untimed():
    t = StageTimings()
    with span("launch", t):
        pass
    with span("execute", t):
        pass
    assert t.launch_ns > 0 and t.execute_ns > 0
    with span("extract", t):            # a span with no field
        pass
    assert t.as_dict() == {**StageTimings().as_dict(),
                           "launch": t.launch_ns, "execute": t.execute_ns}
    assert span("launch") is span("send")   # off: one shared no-op
    assert span("place", t) is span("send")  # no field to fill either
