import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST stay first: jax locks the device count at first
init, and the production meshes need 512 placeholder host devices.

``--comm`` switches to the *transfer-graph* dry-run instead: plan-only
``session.describe(...)`` rows (copy-node/edge counts, critical-path
depth, modeled times) over the standard topologies — no jax device init,
no compilation. ``repro.launch.report`` renders both row kinds.

For every non-skipped cell this driver:

1. builds ``input_specs`` (ShapeDtypeStruct + shardings, no allocation),
2. ``jax.jit(step).lower(...).compile()`` on the 16×16 single-pod mesh AND
   the 2×16×16 multi-pod mesh — the full-depth compile is the pass/fail
   artifact and supplies ``memory_analysis()`` (buffer assignment is
   while-loop-aware, so it is the fits-on-chip proof),
3. derives roofline FLOPs/bytes/collective-bytes by **loop extrapolation**:
   XLA's ``cost_analysis()`` counts a ``while`` body once regardless of trip
   count, so scanned-layer models would be undercounted ×L. We compile L=0
   and L=1 probes of the same cell and extrapolate
   ``total = cost(L0) + Σ_bodies n_i · (cost(L1ᵢ) − cost(L0))`` — gemma3's
   local/global stack uses two body probes (n_local=52, n_global=10),
4. appends the row to ``experiments/dryrun_results.json``.

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun [--arch a] [--shape s]
        [--mesh single|multi|both] [--out f.json] [--skip-existing]
"""

import argparse
import dataclasses
import json
import time
import traceback

from jax import set_mesh


def _cost_tuple(compiled, default_group):
    from repro.launch import roofline
    cost = compiled.cost_analysis()
    stats = roofline.collective_bytes(compiled.as_text(), default_group)
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)),
            stats.total_wire_bytes,
            stats.by_op)


def _merge_by_op(base, body, n):
    out = {k: dict(v) for k, v in base.items()}
    for k, v in body.items():
        d = out.setdefault(k, {"count": 0, "wire_bytes": 0.0})
        d["count"] += n * v["count"]
        d["wire_bytes"] += n * v["wire_bytes"]
    return out


def lower_and_compile(arch, shape, mesh):
    import jax
    from repro.launch.specs import input_specs
    cell = input_specs(arch, shape, mesh)
    # set_mesh (not the legacy `with mesh:`) — it installs the abstract mesh
    # so the model's activation sharding constraints resolve.
    with set_mesh(mesh):
        lowered = jax.jit(cell.fn).lower(*cell.abstract_args)
        compiled = lowered.compile()
    return cell, compiled


def body_probes(arch):
    """[(count, probe_cfg)] covering the layer stack's body types."""
    if arch.attention == "local_global":
        r = arch.local_global_ratio
        n_global = sum(1 for i in range(arch.num_layers) if i % (r + 1) == r)
        n_local = arch.num_layers - n_global
        local = dataclasses.replace(arch, num_layers=1)
        glob = dataclasses.replace(arch, num_layers=1, attention="full",
                                   local_global_ratio=0, window=None)
        return [(n_local, local), (n_global, glob)]
    return [(arch.num_layers, dataclasses.replace(arch, num_layers=1))]


def extrapolated_cost(arch, shape, mesh):
    """(flops, hbm_bytes, wire_bytes, by_op) per device, loop-corrected."""
    base_cfg = dataclasses.replace(arch, num_layers=0)
    _, c0 = lower_and_compile(base_cfg, shape, mesh)
    group = mesh.shape.get("model", 1)
    f0, b0, w0, op0 = _cost_tuple(c0, group)
    flops, bytes_, wire, by_op = f0, b0, w0, {k: dict(v)
                                              for k, v in op0.items()}
    for count, probe_cfg in body_probes(arch):
        _, c1 = lower_and_compile(probe_cfg, shape, mesh)
        f1, b1, w1, op1 = _cost_tuple(c1, group)
        flops += count * max(0.0, f1 - f0)
        bytes_ += count * max(0.0, b1 - b0)
        wire += count * max(0.0, w1 - w0)
        body_ops = {k: {"count": v["count"] - op0.get(k, {}).get("count", 0),
                        "wire_bytes": v["wire_bytes"] -
                        op0.get(k, {}).get("wire_bytes", 0.0)}
                    for k, v in op1.items()}
        by_op = _merge_by_op(by_op, body_ops, count)
    return flops, bytes_, wire, by_op


def run_cell(arch, shape, mesh, mesh_name):
    import jax
    from repro.launch import roofline

    cell, compiled = lower_and_compile(arch, shape, mesh)
    mem = compiled.memory_analysis()
    flops, hbm, wire, by_op = extrapolated_cost(arch, shape, mesh)
    chips = mesh.devices.size
    tokens = shape.global_batch * shape.seq_len
    nap = arch.active_param_count()
    if shape.kind == "train":
        mflops = roofline.train_model_flops(nap, tokens)
    elif shape.kind == "prefill":
        mflops = roofline.prefill_model_flops(nap, tokens)
    else:
        mflops = roofline.decode_model_flops(nap, shape.global_batch)
    mem_bytes = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    from repro.core.topology import (HBM_GBPS, ICI_LINK_GBPS,
                                     PEAK_BF16_TFLOPS)
    compute_s = flops / (PEAK_BF16_TFLOPS * 1e12)
    memory_s = hbm / (HBM_GBPS * 1e9)
    collective_s = wire / (ICI_LINK_GBPS * 1e9)
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    bottleneck = max(terms, key=terms.get)
    row = {
        "arch": arch.name, "shape": shape.name, "mesh": mesh_name,
        "status": "ok", "kind": shape.kind, "chips": chips,
        "description": cell.description,
        "flops": flops, "hbm_bytes": hbm, "wire_bytes": wire,
        "collective_by_op": by_op,
        "compute_s": compute_s, "memory_s": memory_s,
        "collective_s": collective_s, "bottleneck": bottleneck,
        "model_flops": mflops,
        "useful_flops_ratio": (mflops / (flops * chips)
                               if flops else 0.0),
        "memory_per_device_gb": mem_bytes / 2**30,
        "argument_gb": mem.argument_size_in_bytes / 2**30,
        "output_gb": mem.output_size_in_bytes / 2**30,
        "temp_gb": mem.temp_size_in_bytes / 2**30,
        "alias_gb": mem.alias_size_in_bytes / 2**30,
    }
    return row


#: (name, constructor) cells swept by the ``--comm`` transfer-graph dry-run.
def _comm_topologies():
    """(name, topology, (src, dst)) sweep cells; the hierarchical cell
    describes a cross-island transfer so the staged-routing and
    flat-vs-two-level model rows land in the dry-run artifact."""
    from repro.core.topology import Topology
    return [
        ("beluga4", Topology.full_mesh(4), (0, 1)),
        ("narval4", Topology.full_mesh(4, sublinks_per_pair=4,
                                       name="narval4"), (0, 1)),
        ("torus4x4", Topology.torus2d(4, 4), (0, 1)),
        ("hier2x4", Topology.hierarchical(2, 4, egress_per_island=2,
                                          name="hier2x4"), (1, 7)),
    ]


def _route_strs(plan) -> list[str]:
    """``src->via->dst`` strings, one per plan path, in share order."""
    return ["->".join(str(n) for n in (pa.route.hops[0].src,
                                       *(h.dst for h in pa.route.hops)))
            for pa in plan.paths]


def run_comm_dryrun(out_path: str,
                    fail_link: tuple[int, int] | None = None) -> list[dict]:
    """Plan-only sweep: ``session.describe`` over topology × size × paths,
    plus a schedule sweep over the shipped chunk-interleaving passes.

    Every ``comm_graph`` row is one transfer graph — node/edge counts,
    critical-path depth, canonical digest, and the analytic model's
    costs; every ``comm_schedule`` row is one (topology, size, scheduler)
    cell with the scheduled graph's modeled time and its delta vs the
    ``round_robin`` baseline (DESIGN.md §2.2). With ``fail_link`` every
    topology that carries that directional link additionally emits a
    ``comm_fault`` row: the steady-state plan before the fault and the
    surviving-routes re-plan after ``fail_link`` (routes, modeled
    bandwidth, DESIGN §4.6 ladder level), the restore leaving the
    topology untouched. Appended to ``out_path`` (replacing stale comm
    rows) next to the model-cell rows so one JSON feeds
    ``repro.launch.report``.
    """
    from repro.comm import SCHEDULE_NAMES, CommConfig, CommSession

    MiB = 1 << 20
    rows = []
    for topo_name, topo, (src, dst) in _comm_topologies():
        sess = CommSession(CommConfig(multipath_threshold=MiB),
                           topology=topo)
        for nbytes in (1 * MiB, 8 * MiB, 64 * MiB):
            for max_paths in (1, 3):
                d = sess.describe(src, dst, nbytes, max_paths=max_paths)
                row = {"kind": "comm_graph", "status": "ok",
                       "topology": topo_name,
                       "nbytes": nbytes, "max_paths": max_paths,
                       "num_paths": d["num_paths"], **d["graph"],
                       **d["model"],
                       "islands": d["hierarchy"]["islands"],
                       "cross_island": d["hierarchy"]["cross_island"]}
                rows.append(row)
                print(f"COMM {topo_name} {nbytes >> 20}MiB "
                      f"paths={d['num_paths']} nodes={d['graph']['nodes']} "
                      f"edges={d['graph']['edges']} "
                      f"cp={d['graph']['critical_path_nodes']} "
                      f"bw={d['model']['effective_gbps']:.1f}GB/s",
                      flush=True)
        for nbytes in (8 * MiB, 64 * MiB):
            for sched in SCHEDULE_NAMES:
                d = sess.describe(src, dst, nbytes, max_paths=3,
                                  schedule=sched)
                s = d["schedule"]
                rows.append({
                    "kind": "comm_schedule", "status": "ok",
                    "topology": topo_name, "nbytes": nbytes,
                    "schedule": sched, "chosen": s["chosen"],
                    "nodes": d["graph"]["nodes"],
                    "digest": d["graph"]["digest"],
                    "scheduled_time_s": s["scheduled_time_s"],
                    "delta_vs_round_robin_s":
                        s["delta_vs_round_robin_s"],
                })
                print(f"SCHED {topo_name} {nbytes >> 20}MiB "
                      f"{sched}->{s['chosen']} "
                      f"t={s['scheduled_time_s'] * 1e6:.1f}us "
                      f"d={s['delta_vs_round_robin_s'] * 1e9:.0f}ns",
                      flush=True)
        if fail_link is not None:
            fsrc, fdst = fail_link
            try:
                sess.topology.link(fsrc, fdst)
            except KeyError:
                print(f"FAULT {topo_name}: no link {fsrc}->{fdst}, skipped",
                      flush=True)
                continue

            def _cell(level_hint=None):
                d = sess.describe(src, dst, 8 * MiB, max_paths=3)
                plan = sess.plan(src, dst, 8 * MiB, max_paths=3)
                level = (level_hint if level_hint is not None
                         else (1 if d["num_paths"] > 1 else 2))
                return {"num_paths": d["num_paths"],
                        "routes": _route_strs(plan),
                        "effective_gbps": d["model"]["effective_gbps"],
                        "scheduled_time_s":
                            d["schedule"]["scheduled_time_s"],
                        "level": level}

            before = _cell(level_hint=0)
            sess.topology.fail_link(fsrc, fdst)
            after = _cell()
            sess.topology.restore_link(fsrc, fdst)
            rows.append({"kind": "comm_fault", "status": "ok",
                         "topology": topo_name, "nbytes": 8 * MiB,
                         "src": src, "dst": dst,
                         "failed_link": [fsrc, fdst],
                         "before": before, "after": after})
            print(f"FAULT {topo_name} link {fsrc}->{fdst} down: "
                  f"paths {before['num_paths']}->{after['num_paths']} "
                  f"bw {before['effective_gbps']:.1f}->"
                  f"{after['effective_gbps']:.1f}GB/s "
                  f"ladder {before['level']}->{after['level']}",
                  flush=True)
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    results = []
    if os.path.exists(out_path):
        with open(out_path) as f:
            results = json.load(f)
    results = [r for r in results
               if r.get("kind") not in ("comm_graph", "comm_schedule",
                                        "comm_fault")]
    results.extend(rows)
    with open(out_path, "w") as f:
        json.dump(results, f, indent=1)
    print(f"\ncomm dry-run complete: {len(rows)} rows")
    return rows


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--arch", default=None)
    parser.add_argument("--shape", default=None)
    parser.add_argument("--mesh", default="both",
                        choices=["single", "multi", "both"])
    parser.add_argument("--out", default="experiments/dryrun_results.json")
    parser.add_argument("--skip-existing", action="store_true")
    parser.add_argument("--comm", action="store_true",
                        help="transfer-graph dry-run (plan-only, no jax "
                             "device init)")
    parser.add_argument("--fail-link", metavar="SRC:DST", default=None,
                        help="with --comm: also emit before/after re-plan "
                             "rows with the directional link SRC:DST "
                             "failed (DESIGN §4.6 degraded mode)")
    args = parser.parse_args()

    if args.comm:
        fail = None
        if args.fail_link:
            try:
                a, b = args.fail_link.split(":")
                fail = (int(a), int(b))
            except ValueError:
                parser.error("--fail-link expects SRC:DST device ints, "
                             f"got {args.fail_link!r}")
        run_comm_dryrun(args.out, fail_link=fail)
        return
    if args.fail_link:
        parser.error("--fail-link only applies to the --comm dry-run")

    import jax

    from repro.configs import load_all, REGISTRY
    from repro.configs.shapes import SHAPES, skip_reason
    from repro.launch.mesh import make_production_mesh

    assert len(jax.devices()) == 512, (
        "dry-run needs 512 placeholder devices; do not import jax before "
        "this module sets XLA_FLAGS")

    load_all()
    archs = ([REGISTRY[args.arch.replace("-", "_")]] if args.arch
             else [REGISTRY[k] for k in sorted(REGISTRY)])
    shapes = ([SHAPES[args.shape]] if args.shape else list(SHAPES.values()))
    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single_pod_16x16", make_production_mesh()))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi_pod_2x16x16",
                       make_production_mesh(multi_pod=True)))

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    results = []
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    done = {(r["arch"], r["shape"], r["mesh"]) for r in results
            if r.get("status") in ("ok", "skipped")}

    for arch in archs:
        for shape in shapes:
            reason = skip_reason(arch, shape)
            for mesh_name, mesh in meshes:
                key = (arch.name, shape.name, mesh_name)
                if args.skip_existing and key in done:
                    print(f"SKIP(done) {key}", flush=True)
                    continue
                if reason:
                    row = {"arch": arch.name, "shape": shape.name,
                           "mesh": mesh_name, "status": "skipped",
                           "reason": reason}
                    print(f"SKIP {key}: {reason}", flush=True)
                else:
                    t0 = time.time()
                    try:
                        row = run_cell(arch, shape, mesh, mesh_name)
                        row["compile_s"] = round(time.time() - t0, 1)
                        print(f"OK   {key} compile={row['compile_s']}s "
                              f"mem/dev={row['memory_per_device_gb']:.2f}GiB "
                              f"bneck={row['bottleneck']} "
                              f"[c={row['compute_s']*1e3:.1f}ms "
                              f"m={row['memory_s']*1e3:.1f}ms "
                              f"n={row['collective_s']*1e3:.1f}ms] "
                              f"useful={row['useful_flops_ratio']:.2f}",
                              flush=True)
                    except Exception as e:  # noqa: BLE001
                        row = {"arch": arch.name, "shape": shape.name,
                               "mesh": mesh_name, "status": "error",
                               "error": f"{type(e).__name__}: {e}",
                               "traceback": traceback.format_exc()[-2000:],
                               "compile_s": round(time.time() - t0, 1)}
                        print(f"FAIL {key}: {row['error']}", flush=True)
                results = [r for r in results if
                           (r["arch"], r["shape"], r["mesh"]) != key]
                results.append(row)
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)

    ok = sum(1 for r in results if r.get("status") == "ok")
    sk = sum(1 for r in results if r.get("status") == "skipped")
    er = sum(1 for r in results if r.get("status") == "error")
    print(f"\ndry-run complete: ok={ok} skipped={sk} error={er}")
    if er:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
