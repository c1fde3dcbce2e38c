"""Whole-iteration step capture: one heterogeneous graph per iteration.

The paper's CUDA-graph thesis is "capture once, launch many"; the rest of
:mod:`repro.comm` applies it to *communication* only — each transfer is
one fused launch, but an iteration is still a chain of separate compute
launches with transfer dispatches between them. This module closes the
gap: a :class:`StepCapture` records a full step (kernel invocations +
multipath exchanges) against declared buffers, :func:`lower_step` lowers
the recording to ONE heterogeneous
:class:`~repro.comm.graph.TransferGraph` — :class:`~repro.comm.graph
.CopyNode` per chunk per hop plus :class:`~repro.comm.graph.ComputeNode`
per kernel, coupled by ``"buffer"`` def-use edges — and the engine
schedules it with the ordinary §2.2 passes, compiles it as ONE SPMD
program, and launches the whole iteration as ONE dispatch.

Contract highlights (the invariant obligations the §4.5 validator and
the cache layer rely on):

* **Buffers are SSA** — every buffer id is written exactly once (a step
  input, one kernel's result, or one exchange's reception); the lowering
  derives the ``"buffer"`` dependency edges from that def-use relation
  and :meth:`~repro.comm.graph.TransferGraph.validate` re-checks them.
* **Kernel name is identity** — digests, ``GroupKey`` entries, and
  telemetry signatures all key compute work by its registered kernel
  name; registering a different function under a used name raises at
  capture time, because a silently swapped kernel would be served a
  stale executable.
* **Reception values are exact** — inside the SPMD program a reception
  buffer holds the message on its destination device and *zeros*
  elsewhere (``ppermute`` semantics), so summing the per-message
  reception buffers of a ring exchange reconstructs each device's
  received value exactly (adding zeros is exact in IEEE-754 up to the
  sign of zero) — the idiom :func:`captured_psum` and the captured
  Jacobi step build on.
* **Capture signature** — :meth:`StepCapture.signature` is the hashable
  request identity the engine's fast path memoizes resolutions under
  (together with the schedule name and planner epoch), and the scheduled
  graph's :meth:`~repro.comm.graph.TransferGraph.digest` keys the
  compiled executable — two schedules of one captured step digest apart
  and can never cross-serve.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from repro.comm.graph import (BUFFER_EDGE, HOP_EDGE, ComputeNode, CopyNode,
                              DepEdge, TransferGraph)
from repro.comm.telemetry import spanned


@dataclasses.dataclass(frozen=True)
class BufferSpec:
    """Static identity of one step buffer: per-device local shape, dtype
    (canonical string), and whether the step *input* arrives replicated.

    Part of the capture signature, so it must stay hashable and
    canonical (the contract :func:`repro.comm.graph.canonical_digest`
    inherits): two captures with equal specs and ops resolve to the same
    fast-path entry. ``replicated`` only affects input staging — results
    and receptions are always per-device local values.
    """

    shape: tuple[int, ...]
    dtype: str
    replicated: bool = False


@dataclasses.dataclass(frozen=True)
class BufferRef:
    """Opaque handle to a capture buffer (its id in the buffer table).

    Refs are how a step's dataflow is declared — the lowering turns the
    def-use relation over refs into the graph's validated ``"buffer"``
    edges, so holding a ref across captures (or forging ids) breaks the
    SSA contract and fails validation.
    """

    buf_id: int


def _dtype_str(dtype) -> str:
    return str(jnp.dtype(dtype))


class StepCapture:
    """Recorder for one iteration: inputs, kernels, exchanges.

    The builder half of ``session.capture(build_fn)``: ``build_fn``
    receives the capture, declares buffers/ops through the methods
    below, and returns the output ref(s). Nothing executes at capture
    time — the recording is lowered (:func:`lower_step`), scheduled, and
    compiled by the engine on first launch, then memoized by
    :meth:`signature` + planner epoch.

    Invariant obligations: buffers are SSA (each id written once),
    kernel names are identities (re-registering a different function
    under a used name raises), and exchanged payloads must be 1-D
    buffers produced by an input or a kernel (never a raw reception —
    pass receptions through a kernel first, which also gives the §4.5
    validator a compute producer for the next round's buffer edges).
    """

    def __init__(self):
        self.buffers: list[BufferSpec] = []
        self.inputs: list[int] = []
        self.ops: list[tuple] = []
        self.kernels: dict[str, Callable] = {}
        self._receptions: set[int] = set()

    def _new_buffer(self, spec: BufferSpec) -> int:
        self.buffers.append(spec)
        return len(self.buffers) - 1

    def _resolve(self, ref: BufferRef) -> int:
        if not isinstance(ref, BufferRef):
            raise TypeError(f"expected a BufferRef, got {type(ref)!r}")
        if not 0 <= ref.buf_id < len(self.buffers):
            raise ValueError(f"unknown buffer id {ref.buf_id} (refs are "
                             "capture-local; the SSA contract forbids "
                             "sharing them across captures)")
        return ref.buf_id

    def input(self, shape: Sequence[int], dtype=jnp.float32, *,
              replicated: bool = False) -> BufferRef:
        """Declare one step input buffer and return its ref.

        ``shape`` is the per-device *local* shape. ``replicated=False``
        (default) means the caller passes a ``(num_devices, *shape)``
        array sharded on the leading axis; ``replicated=True`` means one
        ``shape``-shaped array every device sees whole. Input order is
        call order — the launch contract aligns positional arrays with
        it.
        """
        bid = self._new_buffer(BufferSpec(tuple(int(s) for s in shape),
                                          _dtype_str(dtype),
                                          bool(replicated)))
        self.inputs.append(bid)
        self.ops.append(("input", bid))
        return BufferRef(bid)

    def kernel(self, fn: Callable, *operands: BufferRef,
               out: BufferSpec | Sequence[BufferSpec] | None = None,
               name: str | None = None, flops: int = 0,
               cost_ns: int = 0):
        """Record one SPMD kernel invocation; returns the result ref(s).

        ``fn`` maps the operands' local values to one array (or a tuple
        of arrays) — it runs on every device inside the compiled
        program. Result specs come from ``jax.eval_shape`` unless ``out``
        is given explicitly (required when ``fn`` uses
        ``jax.lax.axis_index``, which cannot be abstractly evaluated
        outside the mesh). ``name`` (default ``fn.__name__``) is the
        kernel's *identity* — it reaches digests, cache keys, and
        telemetry signatures, so registering a different function under
        a used name raises (the §2.2 identity contract). ``flops`` /
        ``cost_ns`` feed the cost model's
        :class:`~repro.comm.graph.ComputeNode` pricing so ``auto``
        arbitration prices compute honestly.
        """
        kname = name if name is not None else getattr(fn, "__name__",
                                                      "kernel")
        if kname == "<lambda>":
            raise ValueError("anonymous kernels need an explicit name= "
                             "(the name is the cache identity)")
        prior = self.kernels.get(kname)
        if prior is not None and prior is not fn:
            raise ValueError(
                f"kernel name {kname!r} already registered with a "
                f"different function — the name is the digest/cache "
                f"identity and must not be reused")
        ops = tuple(self._resolve(r) for r in operands)
        if out is None:
            args = [jax.ShapeDtypeStruct(self.buffers[b].shape,
                                         jnp.dtype(self.buffers[b].dtype))
                    for b in ops]
            try:
                res = jax.eval_shape(fn, *args)
            except Exception as exc:  # axis_index etc.
                raise ValueError(
                    f"could not infer result specs for kernel {kname!r} "
                    f"(kernels using lax.axis_index must pass out=): "
                    f"{exc}") from exc
            single = not isinstance(res, (tuple, list))
            specs = [BufferSpec(tuple(r.shape), _dtype_str(r.dtype))
                     for r in ((res,) if single else res)]
        else:
            single = isinstance(out, BufferSpec)
            specs = [out] if single else list(out)
        results = tuple(self._new_buffer(s) for s in specs)
        self.kernels[kname] = fn
        self.ops.append(("kernel", kname, ops, results,
                         int(flops), int(cost_ns)))
        refs = tuple(BufferRef(b) for b in results)
        return refs[0] if single else refs

    def exchange(self, sends: Sequence[tuple[BufferRef, int, int]], *,
                 max_paths: int | None = None,
                 num_chunks: int | None = None) -> list[BufferRef]:
        """Record one fused multipath exchange; returns reception refs.

        ``sends`` is one ``(payload_ref, src, dst)`` per message; the
        exchange is planned *jointly* (the engine's ``plan_group``) and
        lowers to the group's copy nodes inside the step graph. Each
        message gets a fresh reception buffer: inside the program it
        holds the full payload on ``dst`` and exact zeros elsewhere (the
        summable-receptions contract in the module docstring). Payloads
        must be 1-D and must not themselves be raw receptions (route
        them through a kernel first — preserves the SSA/def-use
        validation). ``max_paths`` / ``num_chunks`` pass through to the
        planner and are part of the capture signature.
        """
        if not sends:
            raise ValueError("exchange needs at least one message")
        rec: list[tuple[int, int, int]] = []
        results = []
        for (ref, src, dst) in sends:
            bid = self._resolve(ref)
            spec = self.buffers[bid]
            if len(spec.shape) != 1:
                raise ValueError(
                    f"exchange payloads must be 1-D buffers, got shape "
                    f"{spec.shape} (reshape inside a kernel first)")
            if bid in self._receptions:
                raise ValueError(
                    "cannot exchange a raw reception buffer — pass it "
                    "through a kernel first (def-use contract)")
            if src == dst:
                raise ValueError(f"self-send {src}->{dst} in exchange")
            rec.append((bid, int(src), int(dst)))
            rbuf = self._new_buffer(BufferSpec(spec.shape, spec.dtype))
            self._receptions.add(rbuf)
            results.append(rbuf)
        self.ops.append(("exchange", tuple(rec), max_paths, num_chunks,
                         tuple(results)))
        return [BufferRef(b) for b in results]

    def signature(self) -> tuple:
        """Hashable request identity of the recording — buffer table +
        op list (kernel *names*, not functions: the name-is-identity
        contract). Together with the schedule name and the planner
        epoch this keys the engine's fast-path memo, exactly like a
        transfer-group request signature.
        """
        return ("capture",
                tuple(dataclasses.astuple(b) for b in self.buffers),
                tuple(self.ops))


def lower_step(capture: StepCapture, plan_group_fn,
               topology_name: str) -> tuple[TransferGraph, tuple]:
    """Lower a recording to ONE heterogeneous transfer graph.

    Emits nodes in program order (a valid topological order): one
    :class:`~repro.comm.graph.ComputeNode` per kernel invocation, and
    per exchange the jointly-planned group's copy nodes in the paper's
    Algorithm 1 wave order with *global* message indices. Dependency
    edges: ``"hop"`` within chunks, ``"buffer"`` for def-use (producer
    compute → first-hop copies of its payload's messages; terminal
    copies → consumer computes; compute → compute). The graph carries
    the ``messages`` table (msg → payload/reception buffer ids) and is
    §4.5-validated (byte cover per message, hop chains, buffer def-use)
    before being returned together with the flat plan tuple (telemetry
    routes + modeling). ``plan_group_fn(specs, max_paths=, num_chunks=)``
    is the engine's joint planner hook.
    """
    nodes: list = []
    edges: list[DepEdge] = []
    messages: list[tuple[int, int]] = []
    plans_all: list = []
    msg_nbytes: dict[int, int] = {}
    producer: dict[int, int] = {}        # buf -> compute node idx
    terminals_of: dict[int, list[int]] = {}   # reception buf -> copies
    for op in capture.ops:
        if op[0] == "input":
            continue
        if op[0] == "kernel":
            _, kname, operands, results, flops, cost_ns = op
            idx = len(nodes)
            compute_preds = set()
            for b in operands:
                p = producer.get(b)
                if p is not None:
                    compute_preds.add(p)
                for t in terminals_of.get(b, ()):
                    edges.append(DepEdge(t, idx, BUFFER_EDGE))
            for p in sorted(compute_preds):
                edges.append(DepEdge(p, idx, BUFFER_EDGE))
            nodes.append(ComputeNode(kname, 0, operands, results,
                                     flops, cost_ns))
            for r in results:
                producer[r] = idx
            continue
        # exchange
        _, sends, max_paths, num_chunks, results = op
        specs = []
        for (payload, src, dst) in sends:
            spec = capture.buffers[payload]
            specs.append((src, dst, spec.shape[0],
                          jnp.dtype(spec.dtype)))
        group = plan_group_fn(specs, max_paths=max_paths,
                              num_chunks=num_chunks)
        for plan, (payload, _, _), rbuf in zip(group.plans, sends,
                                               results):
            m_idx = len(messages)
            messages.append((payload, rbuf))
            msg_nbytes[m_idx] = plan.nbytes
            plans_all.append(plan)
            flow = (plan.src, plan.dst)
            prod = producer.get(payload)
            terms = terminals_of.setdefault(rbuf, [])
            per_path = [(pa.route.directional_links(), pa.chunk_bounds())
                        for pa in plan.paths]
            waves = max((len(b) for _, b in per_path), default=0)
            for c_idx in range(waves):
                for p_idx, (links, bounds) in enumerate(per_path):
                    if c_idx >= len(bounds):
                        continue
                    off, size = bounds[c_idx]
                    first = len(nodes)
                    for h_idx, link in enumerate(links):
                        k = len(nodes)
                        nodes.append(CopyNode(flow, m_idx, p_idx, c_idx,
                                              h_idx, 0, link, off, size))
                        if h_idx:
                            edges.append(DepEdge(k - 1, k, HOP_EDGE))
                    if prod is not None:
                        edges.append(DepEdge(prod, first, BUFFER_EDGE))
                    terms.append(len(nodes) - 1)
    graph = TransferGraph(tuple(nodes), tuple(edges), 1, len(messages),
                          topology_name, tuple(messages))
    graph.validate(msg_nbytes, cross_flow_exclusive=False)
    return graph, tuple(plans_all)


def emit_step(graph: TransferGraph, buffers: Sequence[BufferSpec],
              kernels: dict, values: dict, axis_name: str) -> dict:
    """Walk a SCHEDULED heterogeneous graph in topological order, one
    ``ppermute`` per copy node and one kernel call per compute node.

    ``values`` maps buffer id → local array for the step inputs; the
    walk fills in kernel results and reception buffers (zeros +
    per-terminal ``dynamic_update_slice``, the §4.5 "final
    synchronization" join) and returns the completed map. Dataflow
    follows the graph's hop and buffer edges exactly — the emitter owns
    no ordering of its own, preserving the §2.2 schedule = node-index
    order invariant.
    """
    values = dict(values)
    preds = graph.hop_predecessor
    terminals = graph.terminal_nodes
    chunk_vals: dict[int, jax.Array] = {}
    for idx in graph.topological_order():
        node = graph.nodes[idx]
        if isinstance(node, ComputeNode):
            args = [values[b] for b in node.operands]
            res = kernels[node.kernel](*args)
            if len(node.results) == 1:
                values[node.results[0]] = res
            else:
                for r, v in zip(node.results, res):
                    values[r] = v
            continue
        payload_id, result_id = graph.messages[node.msg_idx]
        isz = jnp.dtype(buffers[payload_id].dtype).itemsize
        if node.offset % isz or node.nbytes % isz:
            raise ValueError("chunk bounds not element-aligned")
        off_e, size_e = node.offset // isz, node.nbytes // isz
        pred = preds.get(idx)
        if pred is None:
            chunk = jax.lax.slice(values[payload_id], (off_e,),
                                  (off_e + size_e,))
        else:
            chunk = chunk_vals.pop(pred)
        chunk = jax.lax.ppermute(chunk, axis_name, [node.link])
        if idx in terminals:
            spec = buffers[result_id]
            cur = values.get(result_id)
            if cur is None:
                cur = jnp.zeros(spec.shape, jnp.dtype(spec.dtype))
            values[result_id] = jax.lax.dynamic_update_slice(
                cur, chunk, (off_e,))
        else:
            chunk_vals[idx] = chunk
    return values


class CapturedStep:
    """Launchable handle for one captured iteration.

    Calling it stages the inputs and launches the compiled SPMD program
    ONCE — `session.stats()["dispatches"]` increments by exactly one per
    call, the acceptance invariant of whole-iteration capture. Outputs
    come back device-stacked ``(num_devices, *local_shape)``; replicated
    results are row-identical (take row 0). Resolution rides the
    engine's fast path: the capture :meth:`~StepCapture.signature` +
    schedule name + planner epoch memoize the lowered/scheduled/compiled
    entry, and the scheduled graph digest keys the executable — two
    schedules of the same capture digest apart and never cross-serve.
    """

    def __init__(self, engine, capture: StepCapture,
                 outputs: Sequence[BufferRef],
                 schedule: str | None = None):
        self.engine = engine
        self.capture = capture
        self.outputs = tuple(capture._resolve(r) for r in outputs)
        self.schedule = schedule

    def resolve(self, schedule: str | None = None):
        """Resolve (lower → schedule → validate → compile → memoize)
        without launching; returns the fast-path entry whose ``graph``
        (scheduled, digest-keyed) the §2.2 contract checked. Useful for
        inspection and modeled-time evaluation.
        """
        return self.engine.resolve_step(
            self, schedule if schedule is not None else self.schedule)

    @spanned("step")
    def __call__(self, *arrays, schedule: str | None = None,
                 block: bool = True) -> list[jax.Array]:
        """Run one captured iteration as ONE dispatch; ``arrays`` align
        with the capture's declared inputs (sharded inputs are global
        ``(num_devices, *local)``; replicated inputs are bare local
        arrays). Preserves eager numerics — the kernels are the same
        functions, receptions join by exact zero-sum. One ``comm.step``
        span holds the call."""
        return self.engine.run_step(
            self, arrays,
            schedule=schedule if schedule is not None else self.schedule,
            block=block)


def captured_psum(cap: StepCapture, ref: BufferRef, num_devices: int, *,
                  max_paths: int | None = None,
                  num_chunks: int | None = None,
                  name: str | None = None) -> BufferRef:
    """Express a ring all-reduce *sum* of a 1-D buffer as capture ops.

    ``num_devices - 1`` rounds; each round is one fused multipath
    exchange of every device's running value to its right neighbor plus
    one combine kernel that joins the receptions by exact zero-sum (the
    module-docstring contract) and accumulates. The whole collective
    therefore lives inside the SAME step graph as the compute that
    produced ``ref`` — the schedulers interleave its copies into compute
    gaps, and the §4.5 validator checks every round's byte cover and
    buffer def-use. Divide by ``num_devices`` afterwards for a pmean.
    """
    n = int(num_devices)
    if n < 2:
        return ref
    prefix = name if name is not None else f"psum{len(cap.ops)}"
    nelems = cap.buffers[cap._resolve(ref)].shape[0]
    acc, cur = ref, ref
    for r in range(n - 1):
        recvs = cap.exchange([(cur, i, (i + 1) % n) for i in range(n)],
                             max_paths=max_paths, num_chunks=num_chunks)

        def combine(acc_v, *received):
            got = received[0]
            for x in received[1:]:
                got = got + x
            return acc_v + got, got

        acc, cur = cap.kernel(combine, acc, *recvs,
                              name=f"{prefix}_r{r}",
                              flops=(n + 1) * nelems)
    return acc
