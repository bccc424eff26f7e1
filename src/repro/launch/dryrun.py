import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax-importing module: jax locks the device count on
# first init. The dry-run (and only the dry-run) runs with 512 placeholder
# host devices so the production meshes can be built; smoke tests and
# benchmarks see the real single CPU device.
#
# Multi-pod dry-run: for every (architecture x input shape x mesh) cell,
# lower + compile the real train/prefill/serve step with full production
# shardings, prove it fits (memory_analysis) and capture the roofline inputs
# (cost_analysis + collective bytes from the partitioned HLO). Artifacts are
# written one JSON per cell under --out.
import argparse    # noqa: E402
import functools   # noqa: E402
import json        # noqa: E402
import time        # noqa: E402
import traceback   # noqa: E402

import jax                                # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ARCHS, get_config, shapes as shapes_lib  # noqa: E402
from repro.core import jitcache           # noqa: E402
from repro.launch import cost_model       # noqa: E402
from repro.launch import hlo as hlo_lib   # noqa: E402
from repro.launch import roofline as rl   # noqa: E402
from repro.launch import traffic_model    # noqa: E402
from repro.launch.mesh import make_production_mesh, mesh_tag  # noqa: E402
from repro.models import model as model_lib  # noqa: E402
from repro.optim import adamw             # noqa: E402
from repro.train import sharding, steps   # noqa: E402


def _named(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def opt_config_for(cfg) -> adamw.OptConfig:
    return adamw.OptConfig(state_dtype=cfg.param_dtype)


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               correct: bool = True, layout: str = "2d",
               remat: bool | None = None, moe_chunk: int | None = None):
    """Lower + compile one (arch, shape, mesh) cell; returns artifact dict.

    ``correct=False`` skips the corrected-cost compiles (used for the
    multi-pod pass, which only needs the lower+compile proof; the roofline
    table is single-pod).
    """
    cfg = get_config(arch)
    if remat is not None or moe_chunk is not None:
        import dataclasses
        kw = {}
        if remat is not None:
            kw["remat"] = remat
        if moe_chunk is not None:
            kw["moe_seq_chunk"] = moe_chunk
        cfg = dataclasses.replace(cfg, **kw)
    sh = shapes_lib.SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    sharding.set_activation_hints(mesh, batch=sh.batch, layout=layout)

    params_shape = jax.eval_shape(
        lambda: model_lib.init(jax.random.PRNGKey(0), cfg))
    pspecs = sharding.param_specs(cfg, mesh, params_shape, layout)
    pshard = _named(mesh, pspecs)
    specs = shapes_lib.input_specs(cfg, shape_name)

    t0 = time.time()
    if sh.kind == "train":
        ocfg = opt_config_for(cfg)
        opt_shape = jax.eval_shape(
            functools.partial(adamw.init_opt, ocfg=ocfg), params_shape)
        oshard = _named(mesh, sharding.opt_specs(cfg, mesh, pspecs))
        bshard = _named(mesh, sharding.batch_specs(cfg, mesh, layout))
        fn = steps.build_train_step(cfg, ocfg)
        lowered = jax.jit(
            fn, in_shardings=(pshard, oshard, bshard),
            out_shardings=(pshard, oshard, None),
            donate_argnums=(0, 1),
        ).lower(params_shape, opt_shape, specs["batch"])
    elif sh.kind == "prefill":
        inshard = _named(mesh, sharding.prefill_input_specs(cfg, mesh, batch=sh.batch, layout=layout))
        cache_shape = jax.eval_shape(
            lambda: model_lib.init_cache(cfg, sh.batch, sh.seq))
        cshard = _named(mesh, sharding.cache_specs(cfg, mesh, cache_shape, layout))
        fn = steps.build_prefill_step(cfg)
        lowered = jax.jit(
            fn, in_shardings=(pshard, inshard),
            out_shardings=(None, cshard),
        ).lower(params_shape, {k: specs[k] for k in inshard})
    else:  # decode
        cshard = _named(mesh, sharding.cache_specs(cfg, mesh, specs["cache"], layout))
        dshard = _named(mesh, sharding.decode_input_specs(cfg, mesh, batch=sh.batch, layout=layout))
        fn = steps.build_serve_step(cfg)
        lowered = jax.jit(
            fn, in_shardings=(pshard, cshard, dshard["token"], dshard["pos"]),
            out_shardings=(dshard["token"], None, cshard),
            donate_argnums=(1,),
        ).lower(params_shape, specs["cache"], specs["token"], specs["pos"])
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):  # older jaxlibs: one dict per program
        cost = cost[0] if cost else {}
    coll = hlo_lib.collective_bytes(compiled.as_text())

    # corrected accounting: XLA counts while bodies once; compose the true
    # cost from loop-free compiles (see repro.launch.cost_model).
    t0 = time.time()
    if correct:
        corrected = cost_model.corrected_costs(cfg, mesh, shape_name, layout=layout)
    else:
        corrected = {"total": {"flops": float(cost.get("flops", 0.0)),
                               "hbm_bytes": float(cost.get("bytes accessed",
                                                           0.0)),
                               "coll_bytes": coll.total_bytes},
                     "note": "raw whole-program numbers (uncorrected)"}
    t_correct = time.time() - t0

    tokens_global = sh.batch * (sh.seq if sh.kind != "decode" else 1)
    n_params = cfg.active_param_count()
    model_flops = rl.model_flops_per_chip(sh.kind, n_params, tokens_global,
                                          n_chips)
    # memory term: analytic perfect-fusion traffic (TPU-fusion estimate);
    # cost_analysis bytes (CPU-grade fusion) kept alongside as upper bound.
    mesh_axes = dict(zip(mesh.axis_names, (mesh.shape[a]
                                           for a in mesh.axis_names)))
    if layout == "fsdp":  # model axis acts as extra data parallelism
        mesh_axes = {"data": mesh.size, "model": 1}
    tm = traffic_model.traffic(cfg, shape_name, mesh_axes)
    roof = rl.make_roofline(
        flops=corrected["total"]["flops"],
        hbm_bytes=tm["total"],
        coll_bytes=corrected["total"]["coll_bytes"],
        model_flops=model_flops)

    art = {
        "arch": arch, "shape": shape_name, "kind": sh.kind,
        "layout": layout,
        "mesh": mesh_tag(mesh), "n_chips": n_chips,
        "seq": sh.seq, "global_batch": sh.batch,
        "params": cfg.param_count(), "active_params": n_params,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "correct_s": round(t_correct, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            # older jaxlibs don't expose peak; args+out+temp is the bound
            "peak_bytes": getattr(mem, "peak_memory_in_bytes",
                                  mem.argument_size_in_bytes
                                  + mem.output_size_in_bytes
                                  + mem.temp_size_in_bytes),
            "total_per_device": mem.argument_size_in_bytes
            + mem.output_size_in_bytes + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes,
        },
        "cost_raw_whole_program": {  # while bodies counted once (XLA quirk)
            k: float(v) for k, v in cost.items()
            if k in ("flops", "bytes accessed", "transcendentals")},
        "collectives_raw": coll.summary(),
        "cost_corrected": corrected,
        "hbm_traffic_model": {k: (float(v) if not isinstance(v, int) else v)
                              for k, v in tm.items()},
        "roofline": roof.to_dict(),
    }
    return art


def cells(arch_filter: str, shape_filter: str):
    for arch in ARCHS:
        if arch_filter not in ("all", arch):
            continue
        cfg = get_config(arch)
        for shape_name in shapes_lib.shape_cells(cfg):
            if shape_filter in ("all", shape_name):
                yield arch, shape_name


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["pod1", "pod2", "both"])
    ap.add_argument("--out", default="runs/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--no-correct", action="store_true",
                    help="skip corrected-cost compiles (multi-pod pass)")
    ap.add_argument("--layout", default="2d", choices=["2d", "fsdp", "serve"])
    ap.add_argument("--no-remat", action="store_true",
                    help="disable activation rematerialization")
    ap.add_argument("--moe-chunk", type=int, default=0,
                    help="MoE dispatch window (0 = whole sequence)")
    args = ap.parse_args()
    jitcache.enable_persistent_cache()

    os.makedirs(args.out, exist_ok=True)
    meshes = {"pod1": [False], "pod2": [True], "both": [False, True]}[args.mesh]
    failures = []
    for arch, shape_name in cells(args.arch, args.shape):
        for multi_pod in meshes:
            tag = "2x16x16" if multi_pod else "16x16"
            if args.layout != "2d":
                tag += f"__{args.layout}"
            if args.no_remat:
                tag += "__noremat"
            if args.moe_chunk:
                tag += f"__moechunk{args.moe_chunk}"
            path = os.path.join(args.out, f"{arch}__{shape_name}__{tag}.json")
            if args.skip_existing and os.path.exists(path):
                print(f"skip {path}")
                continue
            print(f"=== {arch} x {shape_name} x {tag}", flush=True)
            try:
                art = lower_cell(arch, shape_name, multi_pod,
                                 correct=not args.no_correct,
                                 layout=args.layout,
                                 remat=False if args.no_remat else None,
                                 moe_chunk=args.moe_chunk or None)
            except Exception as e:  # noqa: BLE001 - report and continue
                failures.append((arch, shape_name, tag, repr(e)))
                print(f"FAILED: {e}\n{traceback.format_exc()}", flush=True)
                continue
            with open(path, "w") as f:
                json.dump(art, f, indent=1)
            m = art["memory"]
            r = art["roofline"]
            print(f"  bytes/dev: args={m['argument_bytes']/2**30:.2f}GiB "
                  f"temp={m['temp_bytes']/2**30:.2f}GiB "
                  f"total={m['total_per_device']/2**30:.2f}GiB", flush=True)
            print(f"  flops/dev={r['flops']:.3e} hbm={r['hbm_bytes']:.3e} "
                  f"coll={r['coll_bytes']:.3e}", flush=True)
            print(f"  roofline: compute={r['compute_s']*1e3:.2f}ms "
                  f"memory={r['memory_s']*1e3:.2f}ms "
                  f"collective={r['collective_s']*1e3:.2f}ms "
                  f"-> {r['bound']}-bound, MFU={r['mfu']*100:.1f}%", flush=True)
    if failures:
        print("\nFAILURES:")
        for f in failures:
            print(" ", f)
        return 1
    print("\nall cells compiled OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
