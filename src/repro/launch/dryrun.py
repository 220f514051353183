import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# A CPU-only tool: it compiles for 512 host devices and must never take
# a chip, which belongs to one process at a time.  The --all children
# inherit this environment.
os.environ["JAX_PLATFORMS"] = "cpu"
# The lines above MUST run before any other import (jax locks the
# platform and device count at first init).  Do not move them.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. constructs the real step function (train sync-step with the X-STCC
     engine, or serve prefill/decode) over ShapeDtypeStruct stand-ins —
     no allocation anywhere,
  3. ``jit(...).lower(...).compile()`` — sharding/memory bugs surface
     here as hard failures; ``memory_analysis()`` proves per-device fit,
  4. derives §Roofline terms.  XLA's ``cost_analysis()`` counts a
     ``lax.scan`` body ONCE (verified), so FLOPs/bytes/collectives are
     measured by *depth extrapolation*: the same program is compiled
     unrolled at depth 1 and depth 2 and the per-layer slope is scaled
     to the full depth — exact for the homogeneous layer stacks used
     throughout (cost(L) = intercept + L x slope),
  5. prices 1000 steps with the paper's monetary cost model, splitting
     collective traffic intra-pod (intra-DC, free) vs inter-pod
     (inter-DC, billed) from the replica groups in the optimized HLO.

Usage:
  python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both
"""

import argparse
import dataclasses
import json
import subprocess
import sys
import time
import traceback


def depth_info(cfg):
    """(full_groups, cfg_at_depth(g)) — the homogeneous-stack knob."""
    if cfg.family in ("dense", "moe", "vlm"):
        per = cfg.moe_interleave if cfg.n_experts else 1
        full = cfg.n_layers // per
        mk = lambda g: dataclasses.replace(cfg, n_layers=g * per)
    elif cfg.family == "hybrid":
        per = cfg.attn_every if cfg.attn_every else cfg.n_layers
        full = cfg.n_layers // per
        rem = cfg.n_layers % per
        mk = lambda g: dataclasses.replace(cfg, n_layers=g * per + rem)
    elif cfg.family == "ssm":
        full = cfg.n_layers
        mk = lambda g: dataclasses.replace(cfg, n_layers=g)
    else:  # audio: encoder and decoder stacks vary together
        full = cfg.n_layers
        mk = lambda g: dataclasses.replace(
            cfg, n_layers=g, n_encoder_layers=g)
    return full, mk


def _lower_cell(cfg, shape, mesh, args):
    """Build + lower the step program for one cell.  Returns lowered."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import cache_specs, input_specs
    from repro.core import policy_for
    from repro.launch.mesh import n_pods as mesh_pods
    from repro.models import build_model
    from repro.models.sharding import params_shardings
    from repro.optim import AdamWConfig
    from repro.train.train_step import make_train_fns

    pods = mesh_pods(mesh)
    model = build_model(cfg)

    def repl(tree):
        return jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(
                l.shape, l.dtype, sharding=NamedSharding(mesh, P())),
            tree,
        )

    def with_param_shardings(tree, pod_prefix: bool):
        inner = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(
                l.shape[1:] if pod_prefix else l.shape, l.dtype),
            tree)
        shardings = params_shardings(inner, cfg)

        def mk(l, s):
            spec = s.spec if s is not None else P()
            if pod_prefix:
                spec = P("pod" if pods > 1 else None, *spec)
            return jax.ShapeDtypeStruct(
                l.shape, l.dtype, sharding=NamedSharding(mesh, spec))

        return jax.tree.map(mk, tree, shardings)

    if shape.kind == "train":
        policy = policy_for(args.policy, delta_steps=args.delta,
                            compress_inter_pod=args.compress)
        opt_cfg = AdamWConfig(state_dtype=cfg.optimizer_state_dtype)
        fns = make_train_fns(model, opt_cfg, policy, pods)
        state_abs = jax.eval_shape(fns.init, jax.random.key(0))
        state_abs = state_abs._replace(
            params=with_param_shardings(state_abs.params, True),
            opt=state_abs.opt._replace(
                mu=with_param_shardings(state_abs.opt.mu, True),
                nu=with_param_shardings(state_abs.opt.nu, True),
                count=repl(state_abs.opt.count),
            ),
            sync=repl(state_abs.sync),
            step=repl(state_abs.step),
        )
        flat = input_specs(cfg, shape, mesh=None)
        assert shape.global_batch % pods == 0

        def pod_split(l):
            spec = P("pod" if pods > 1 else None, "data",
                     *([None] * (l.ndim - 1)))
            return jax.ShapeDtypeStruct(
                (pods, l.shape[0] // pods) + l.shape[1:], l.dtype,
                sharding=NamedSharding(mesh, spec))

        batch_abs = {k: pod_split(v) for k, v in flat.items()}
        step_fn = fns.sync_step if args.program == "sync" else fns.local_step
        return jax.jit(step_fn, donate_argnums=(0,)).lower(
            state_abs, batch_abs)

    from repro.models import sharding as shlib

    shlib.set_pod_vmap(False)  # serve programs are not pod-vmapped
    # Serving layout: weights replicated over 'data' (TP-only) when the
    # per-device model shard fits comfortably — FSDP-sharded weights
    # would be all-gathered EVERY decode step (measured: 61 GB wire per
    # step on qwen2 decode_32k, §Perf).  Very large models (llama4-400B)
    # keep FSDP: the gather is the price of fitting at all.
    model_shards = int(mesh.shape.get("model", 1))
    per_dev_gb = 2.0 * cfg.param_count() / max(model_shards, 1) / 1e9
    serve_cfg = (cfg if per_dev_gb > 4.0
                 else dataclasses.replace(cfg, fsdp_params=False))
    params_abs = with_param_shardings(
        jax.eval_shape(model.init, jax.random.key(0)), False)

    def reshard_serving(tree):
        if serve_cfg is cfg:
            return tree
        shardings = params_shardings(
            jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype),
                         tree), serve_cfg)
        return jax.tree.map(
            lambda l, s: jax.ShapeDtypeStruct(
                l.shape, l.dtype,
                sharding=(s if s is not None else NamedSharding(mesh, P()))),
            tree, shardings)

    params_abs = reshard_serving(params_abs)
    if shape.kind == "prefill":
        batch_abs = input_specs(cfg, shape, mesh=mesh)
        return jax.jit(model.prefill).lower(params_abs, batch_abs)

    cache_abs = cache_specs(cfg, shape, mesh=mesh)
    b = shape.global_batch
    tok_axes = ("pod", "data") if pods > 1 else ("data",)
    tok_n = 1
    for a in tok_axes:
        tok_n *= int(mesh.shape.get(a, 1))
    tok_spec = (tok_axes if b % tok_n == 0 else
                ("data",) if b % int(mesh.shape.get("data", 1)) == 0
                else None)
    tok_abs = jax.ShapeDtypeStruct(
        (b, 1), jnp.int32,
        sharding=NamedSharding(mesh, P(tok_spec, None)))
    return jax.jit(model.decode_step, donate_argnums=(1,)).lower(
        params_abs, cache_abs, tok_abs)


def _measure(compiled, pod_size):
    from repro.launch import roofline as rl

    cost = compiled.cost_analysis()
    colls = rl.parse_collectives(compiled.as_text(), pod_size=pod_size)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll_total": sum(c.wire_bytes for c in colls),
        "coll_inter": sum(c.wire_bytes for c in colls if c.spans_pods),
        "n_colls": len(colls),
    }


def _cell(arch: str, shape_name: str, mesh_kind: str, args) -> dict:
    import jax

    from repro.configs import (
        SHAPES_BY_NAME, adjust_config, get_config, shapes_for,
    )
    from repro.launch import roofline as rl
    from repro.launch.mesh import make_production_mesh, n_pods as mesh_pods
    from repro.models import sharding as shlib

    t0 = time.time()
    shape = SHAPES_BY_NAME[shape_name]
    cfg0 = get_config(arch)
    if shape_name not in [s.name for s in shapes_for(cfg0)]:
        return {
            "arch": arch, "shape": shape_name, "mesh": mesh_kind,
            "status": "skipped",
            "reason": "long_500k requires a sub-quadratic path "
                      "(DESIGN.md §6); full-attention arch",
        }
    cfg = adjust_config(cfg0, shape)
    cfg = dataclasses.replace(
        cfg, dtype="bfloat16", scan_layers=True,
        remat=args.remat, decode_comm=args.decode_comm,
    )

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    pods = mesh_pods(mesh)
    n_chips = int(len(mesh.devices.flat))
    pod_size = n_chips // pods if pods > 1 else None

    if getattr(args, "sp_residual", False):
        from repro.models.sharding import set_rule

        set_rule("residual", "model")

    with shlib.use_mesh(mesh):
        # 1) Full-depth scanned program: the deployable artifact —
        #    memory analysis + the actual collective schedule.
        lowered = _lower_cell(cfg, shape, mesh, args)
        t_lower = time.time()
        compiled = lowered.compile()
        t_compile = time.time()
        mem = compiled.memory_analysis()

        # 2) Depth-1/2 unrolled probes -> per-layer cost slope.
        full_groups, mk = depth_info(cfg)
        probes = []
        probe_times = []
        for g in (1, 2):
            pcfg = dataclasses.replace(
                mk(g), scan_layers=False, unroll_scans=True)
            pl = _lower_cell(pcfg, shape, mesh, args)
            pc = pl.compile()
            probes.append(_measure(pc, pod_size))
            probe_times.append(time.time())

    def extrap(key):
        c1, c2 = probes[0][key], probes[1][key]
        # Clamp: XLA occasionally optimizes the depth-2 probe harder than
        # depth-1 (negative slope); costs are physically monotone in depth.
        return max(c1 + (full_groups - 1) * (c2 - c1), max(c1, c2, 0.0))

    roof = rl.Roofline(
        flops_per_device=extrap("flops"),
        bytes_per_device=extrap("bytes"),
        collective_bytes_total=extrap("coll_total"),
        inter_pod_bytes=extrap("coll_inter"),
        intra_pod_bytes=extrap("coll_total") - extrap("coll_inter"),
        n_chips=n_chips,
        model_flops=rl.model_flops_for(cfg, shape),
    )

    from repro.core.cost_model import TPU_PRICING, training_run_cost

    cost = training_run_cost(
        n_chips=n_chips,
        step_time_s=roof.step_time_s,
        n_steps=1000,
        inter_pod_bytes_per_step=roof.inter_pod_bytes,
        intra_pod_bytes_per_step=roof.intra_pod_bytes,
        ckpt_bytes=2.0 * cfg.param_count(),
        ckpt_every=100,
        pricing=TPU_PRICING,
    )

    hbm_per_chip = 16e9
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    return {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_kind,
        "status": "ok",
        "program": args.program if shape.kind == "train" else shape.kind,
        "policy": args.policy if shape.kind == "train" else None,
        "n_chips": n_chips,
        "n_pods": pods,
        "param_count": cfg.param_count(),
        "active_param_count": cfg.active_param_count(),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "used_bytes_per_device": used,
            "hbm_per_chip": hbm_per_chip,
            "fits": bool(used <= hbm_per_chip),
        },
        "roofline": roof.as_dict(),
        "probe_depths": {"d1": probes[0], "d2": probes[1],
                         "full_groups": full_groups},
        "monetary_cost_1000_steps": cost.as_dict(),
        "timing": {
            "lower_s": t_lower - t0,
            "compile_s": t_compile - t_lower,
            "probes_s": probe_times[-1] - t_compile,
        },
    }


def run_cell(arch, shape_name, mesh_kind, args) -> dict:
    try:
        return _cell(arch, shape_name, mesh_kind, args)
    except Exception as e:  # noqa: BLE001 — a dry-run failure IS the signal
        return {
            "arch": arch, "shape": shape_name, "mesh": mesh_kind,
            "status": "error",
            "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc(limit=20),
        }


def all_cells(mesh_kinds):
    from repro.configs import get_config, list_archs, shapes_for

    for arch in list_archs():
        for shape in shapes_for(get_config(arch)):
            for mk in mesh_kinds:
                yield arch, shape.name, mk


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--policy", default="X_STCC")
    ap.add_argument("--delta", type=int, default=8)
    ap.add_argument("--compress", default="none",
                    choices=("none", "int8", "topk"))
    ap.add_argument("--program", default="sync", choices=("sync", "local"))
    ap.add_argument("--remat", default="full",
                    choices=("none", "full", "selective"))
    ap.add_argument("--decode-comm", default="xla",
                    choices=("xla", "lse_shardmap"))
    ap.add_argument("--sp-residual", action="store_true",
                    help="keep the residual stream sequence-sharded over "
                         "'model' (full SP; §Perf iteration)")
    ap.add_argument("--out-dir", default="results/dryrun")
    ap.add_argument("--tag", default="")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("-j", "--jobs", type=int, default=1)
    args = ap.parse_args()

    mesh_kinds = ("single", "multi") if args.mesh == "both" else (args.mesh,)
    os.makedirs(args.out_dir, exist_ok=True)
    tag = f"__{args.tag}" if args.tag else ""

    if args.all:
        cells = list(all_cells(mesh_kinds))
        procs = []
        failures = 0

        def reap(block=False):
            nonlocal failures
            for p, name in list(procs):
                if block:
                    p.wait()
                if p.poll() is not None:
                    procs.remove((p, name))
                    if p.returncode != 0:
                        failures += 1
                        print(f"[FAIL] {name} rc={p.returncode}", flush=True)

        for arch, shape_name, mk in cells:
            out = os.path.join(
                args.out_dir, f"{mk}__{arch}__{shape_name}{tag}.json")
            if args.skip_existing and os.path.exists(out):
                try:
                    if json.load(open(out)).get("status") in ("ok", "skipped"):
                        continue
                except Exception:
                    pass
            cmd = [
                sys.executable, "-m", "repro.launch.dryrun",
                "--arch", arch, "--shape", shape_name, "--mesh", mk,
                "--policy", args.policy, "--delta", str(args.delta),
                "--compress", args.compress, "--program", args.program,
                "--remat", args.remat, "--decode-comm", args.decode_comm,
                "--out-dir", args.out_dir,
            ] + (["--tag", args.tag] if args.tag else [])
            while len(procs) >= args.jobs:
                time.sleep(1.0)
                reap()
            print(f"[dryrun] {mk} {arch} {shape_name}", flush=True)
            procs.append((
                subprocess.Popen(cmd, env={**os.environ, "JAX_PLATFORMS": "cpu"}),
                f"{mk}/{arch}/{shape_name}",
            ))
        while procs:
            time.sleep(1.0)
            reap()
        print(f"dry-run sweep done; {failures} subprocess failures")
        return 1 if failures else 0

    assert args.arch and args.shape, "--arch and --shape (or --all)"
    rc = 0
    for mk in mesh_kinds:
        res = run_cell(args.arch, args.shape, mk, args)
        out = os.path.join(
            args.out_dir, f"{mk}__{args.arch}__{args.shape}{tag}.json")
        with open(out, "w") as f:
            json.dump(res, f, indent=2)
        status = res["status"]
        extra = ""
        if status == "ok":
            r = res["roofline"]
            extra = (f" dom={r['dominant']} step={r['step_time_s']:.4f}s "
                     f"mfu={r['mfu']:.3f} fits={res['memory']['fits']}")
        elif status == "error":
            extra = " " + res["error"][:200]
            rc = 1
        print(f"[{status}] {mk} {args.arch} {args.shape}{extra}", flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
