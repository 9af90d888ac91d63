import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production meshes, print memory/cost analysis, and dump the roofline
record to benchmarks/results/<arch>__<shape>__<mesh>.json.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --reshard

The XLA flag above MUST precede every other import: jax locks the device
count on first initialization.
"""
import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402

from repro.configs import ASSIGNED_ARCHS, INPUT_SHAPES  # noqa: E402
from repro.launch import analysis  # noqa: E402
from repro.launch.mesh import make_mesh, make_production_mesh  # noqa: E402
from repro.launch.specs import SkipPair, build_program, reshard_program  # noqa: E402

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "benchmarks", "results")


def _tokens(shape_name: str) -> int:
    sc = INPUT_SHAPES[shape_name]
    if sc.kind == "decode":
        return sc.global_batch          # one token per sequence
    return sc.global_batch * sc.seq_len


def run_pair(arch: str, shape_name: str, *, multi_pod: bool = False,
             gen_mode: str = "2d", verbose: bool = True,
             tag: str = "") -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    chips = mesh.devices.size
    t0 = time.perf_counter()
    try:
        fn, args, in_shard, out_shard, meta = build_program(
            arch, shape_name, mesh, gen_mode=gen_mode)
    except SkipPair as e:
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "status": "skipped", "reason": str(e)}
        _save(rec, arch, shape_name, mesh_name, tag)
        if verbose:
            print(f"SKIP {arch} × {shape_name} × {mesh_name}: {e}")
        return rec

    with jax.set_mesh(mesh):
        lowered = jax.jit(fn, in_shardings=in_shard,
                          out_shardings=out_shard).lower(*args)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

    mem = compiled.memory_analysis()
    if verbose:
        print(f"== {arch} × {shape_name} × {mesh_name} ({meta['kind']}) ==")
        print(mem)                       # proves it fits (or not)
        ca = compiled.cost_analysis() or {}
        print({k: ca[k] for k in ("flops", "bytes accessed") if k in ca})

    sc = INPUT_SHAPES[shape_name]
    roof = analysis.analyze(arch, shape_name, mesh_name, chips,
                            meta["cfg"], compiled, _tokens(shape_name),
                            kind=meta["kind"], global_batch=sc.global_batch,
                            seq_len=sc.seq_len,
                            capacity=meta.get("capacity", 0))
    rec = roof.as_dict()
    rec.update(status="ok", kind=meta["kind"],
               lower_s=t_lower, compile_s=t_compile, gen_mode=gen_mode)
    rec["cfg"] = None  # not JSON-serializable; arch name suffices
    del rec["memory_stats"]["alias_bytes"]
    rec["memory_stats"] = roof.memory_stats
    _save(rec, arch, shape_name, mesh_name, tag)
    if verbose:
        print(f"roofline: compute={roof.compute_s*1e3:.2f}ms "
              f"memory={roof.memory_s*1e3:.2f}ms "
              f"collective={roof.collective_s*1e3:.2f}ms "
              f"dominant={roof.dominant} "
              f"useful_ratio={roof.useful_ratio:.2f} "
              f"[lower {t_lower:.0f}s compile {t_compile:.0f}s]")
    return rec


def run_reshard(arch: str, *, multi_pod: bool = False, gen_mode: str = "tp",
                verbose: bool = True) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    fn, args, in_shard, out_shard, meta = reshard_program(
        arch, mesh, gen_mode=gen_mode)
    with jax.set_mesh(mesh):
        compiled = jax.jit(fn, in_shardings=in_shard,
                           out_shardings=out_shard).lower(*args).compile()
    stats = analysis.parse_collectives(compiled.as_text())
    rec = {
        "arch": arch, "shape": f"reshard_{gen_mode}", "mesh": mesh_name,
        "status": "ok", "kind": "reshard",
        "collective_bytes_per_device": stats.modeled_bytes,
        "collectives_by_kind": stats.by_kind(),
        "collective_s": stats.modeled_bytes / analysis.TPU_V5E.ici_bw,
    }
    _save(rec, arch, f"reshard_{gen_mode}", mesh_name, "")
    if verbose:
        print(f"== reshard {arch} × {mesh_name} -> {gen_mode} ==")
        print(f"collective bytes/device: {stats.modeled_bytes/1e9:.3f} GB "
              f"-> {rec['collective_s']*1e3:.1f} ms over ICI")
    return rec


def _save(rec: dict, arch: str, shape: str, mesh_name: str, tag: str):
    os.makedirs(RESULTS_DIR, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    path = os.path.join(
        RESULTS_DIR, f"{arch}__{shape}__{mesh_name}{suffix}.json")
    clean = {k: v for k, v in rec.items() if k != "cfg"}
    with open(path, "w") as f:
        json.dump(clean, f, indent=1, default=str)


def run_pipeline_demo(arch: str = "yi-6b", microbatches: int = 8,
                      verbose: bool = True) -> dict:
    """PP demo: lower + compile a pipelined LM train step on a
    (pipe=4, data=8, model=8) = 256-chip mesh — proves the paper's "PP"
    feature composes with the rest of the stack at production scale."""
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.launch.specs import params_structs
    from repro.models import layers as Lx
    from repro.models import transformer as T
    from repro.sharding.pipeline import pipeline_forward

    cfg = get_config(arch)
    mesh = make_mesh((4, 8, 8), ("pipe", "data", "model"))
    pstruct = params_structs(cfg)
    b, s = 32, 4096
    mb = b // microbatches

    def layer_fn(lp, h, cos, sin):
        return T._layer_train(cfg, lp, h, cos, sin)

    def loss_fn(params, tokens, cos, sin):
        x = Lx.embed_tokens(params, cfg, tokens)
        x = pipeline_forward(layer_fn, params["layers"], x, mesh,
                             microbatches=microbatches, consts=(cos, sin))
        x = Lx.norm_apply(params["ln_f"], cfg, x)
        logits = Lx.unembed(params, cfg, x)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        tgt = jnp.take_along_axis(lp[:, :-1], tokens[:, 1:, None],
                                  axis=-1)[..., 0]
        return -jnp.mean(tgt)

    grad_fn = jax.grad(loss_fn)
    tok = jax.ShapeDtypeStruct((b, s), jnp.int32)
    cos, sin = jax.eval_shape(
        lambda: T._rope(cfg, T._positions(cfg, mb, s)))
    with jax.set_mesh(mesh):
        compiled = jax.jit(grad_fn).lower(
            pstruct, tok,
            jax.ShapeDtypeStruct(cos.shape, cos.dtype),
            jax.ShapeDtypeStruct(sin.shape, sin.dtype)).compile()
    stats = analysis.parse_collectives(compiled.as_text())
    rec = {"arch": arch, "shape": f"pipeline_mb{microbatches}",
           "mesh": "4x8x8", "status": "ok", "kind": "pipeline",
           "collective_bytes_per_device": stats.modeled_bytes,
           "bubble_fraction": (4 - 1) / (microbatches + 4 - 1)}
    _save(rec, arch, f"pipeline_mb{microbatches}", "4x8x8", "")
    if verbose:
        print(f"== pipeline demo {arch} × 4x8x8 mesh (mb={microbatches}) ==")
        print(compiled.memory_analysis())
        print(f"collective bytes/device {stats.modeled_bytes/1e9:.2f} GB, "
              f"bubble {(4-1)/(microbatches+3):.1%}")
    return rec


def run_graphs() -> None:
    """Print the declared RL dataflow graphs (paper Fig. 1 as RLGraph) —
    the static view of what the GraphExecutor schedules; no compilation."""
    from repro.core.partial import build_partial_graph
    from repro.core.ppo_trainer import build_ppo_graph
    from repro.core.trainer import build_grpo_graph

    for build in (build_grpo_graph, build_ppo_graph, build_partial_graph):
        g = build()
        print(g.describe())
        print("  edges:")
        for src, fld, dst in g.edges():
            print(f"    {src} --{fld}--> {dst}")
        print()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(INPUT_SHAPES) + [None])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--gen-mode", default="2d", choices=["2d", "tp"])
    ap.add_argument("--reshard", action="store_true")
    ap.add_argument("--pipeline", action="store_true")
    ap.add_argument("--graph", action="store_true",
                    help="print the declared RL dataflow graphs and exit")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    if args.graph:
        run_graphs()
        return

    if args.pipeline:
        run_pipeline_demo(args.arch or "yi-6b")
        return

    if args.reshard:
        archs = [args.arch] if args.arch else ASSIGNED_ARCHS
        for a in archs:
            run_reshard(a, multi_pod=args.multi_pod, gen_mode="tp")
        return

    pairs = []
    if args.all:
        for a in ASSIGNED_ARCHS:
            for s in INPUT_SHAPES:
                pairs.append((a, s))
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        pairs = [(args.arch, args.shape)]

    failures = []
    for a, s in pairs:
        try:
            run_pair(a, s, multi_pod=args.multi_pod, gen_mode=args.gen_mode,
                     tag=args.tag)
        except Exception as e:  # noqa: BLE001
            traceback.print_exc()
            failures.append((a, s, str(e)))
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for a, s, e in failures:
            print(f"  {a} × {s}: {e[:200]}")
        raise SystemExit(1)
    print("\nall pairs lowered + compiled OK")


if __name__ == "__main__":
    main()
