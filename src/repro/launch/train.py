"""Training launcher — end-to-end GRPO on a selectable architecture.

Smoke widths (runs on the CPU):
    PYTHONPATH=src python -m repro.launch.train --arch yi-6b --smoke \
        --iterations 50 --global-batch 8

Without ``--smoke`` it trains the published config on one device, which
must hold it: yi-6b's 32 layers do not fit one 16 GB TPU v5e chip
(``chip_smoke.py`` runs its published widths at 2 layers).  Production
meshes are compiled, not run, by ``python -m repro.launch.dryrun``.
"""
from __future__ import annotations

import argparse
import json
import time

from repro.configs import ALL_ARCHS, get_config, get_smoke_config
from repro.configs.base import RLConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ALL_ARCHS)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--algorithm", default="grpo",
                    choices=["grpo", "dapo", "ppo"])
    ap.add_argument("--iterations", type=int, default=20)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--num-generations", type=int, default=4)
    ap.add_argument("--max-prompt-len", type=int, default=16)
    ap.add_argument("--max-response-len", type=int, default=16)
    ap.add_argument("--lr", type=float, default=2e-4)
    ap.add_argument("--kl-coef", type=float, default=1e-3)
    ap.add_argument("--temperature", type=float, default=1.0,
                    help="sampling temperature for rollout generation")
    ap.add_argument("--clip-eps", type=float, default=0.2,
                    help="PPO/GRPO ratio clip epsilon (DAPO uses "
                         "clip_eps_high for the upper side)")
    ap.add_argument("--serve-max-slots", type=int, default=0,
                    help="serving engine slot count (0 = RLConfig default)")
    ap.add_argument("--serve-block-size", type=int, default=0,
                    help="paged KV cache block size in tokens "
                         "(0 = RLConfig default)")
    ap.add_argument("--num-nodes", type=int, default=4)
    ap.add_argument("--no-transfer-dock", action="store_true")
    ap.add_argument("--no-allgather-swap", action="store_true")
    ap.add_argument("--no-stage-fusion", action="store_true",
                    help="dispatch independent ready graph nodes "
                         "sequentially instead of concurrently")
    ap.add_argument("--partial-rollout", action="store_true",
                    help="budgeted long-tail generation across iterations "
                         "(runs on the continuous-batching serving engine; "
                         "resume = mid-sequence re-prefill)")
    ap.add_argument("--rollout-engine", default=None,
                    choices=["sync", "serving"],
                    help="generation engine (default: RLConfig default; "
                    "partial rollout always uses serving)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable serving prefix-cache block sharing")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="serving chunked prefill: max prefill tokens per "
                    "engine step (0 = whole-prompt admission)")
    ap.add_argument("--host-tier-blocks", type=int, default=0,
                    help="host-RAM KV tier capacity in blocks (0 = off): "
                    "preempted/suspended KV swaps to host and back instead "
                    "of being recomputed")
    ap.add_argument("--serve-sampling-seed", type=int, default=0,
                    help="run key for counter-based per-request sampling "
                    "streams (serve_sampling_seed): same seed => bitwise "
                    "replayable rollouts, independent of scheduling")
    ap.add_argument("--serve-top-p", type=float, default=1.0,
                    help="nucleus sampling mass, fused into the decode "
                    "step (serve_top_p; 1.0 = off; both engines)")
    ap.add_argument("--serve-top-k", type=int, default=0,
                    help="top-k truncation before sampling (serve_top_k; "
                    "0 = off; both engines)")
    ap.add_argument("--rollout-budget", type=int, default=8,
                    help="tokens per sequence per iteration "
                         "(--partial-rollout)")
    ap.add_argument("--print-graph", action="store_true",
                    help="print the declared RLGraph and exit")
    ap.add_argument("--task", default="pattern",
                    choices=["pattern", "arithmetic"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome-trace/Perfetto JSON of the run "
                    "(stage spans, serving steps, dock byte counters) — "
                    "open at ui.perfetto.dev; see docs/observability.md")
    ap.add_argument("--log-json", default=None)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=0, metavar="N",
                    help="also snapshot full train state to --checkpoint "
                    "after every N completed iterations (enables exact "
                    "--resume mid-run; see docs/resilience.md)")
    ap.add_argument("--resume", default=None,
                    help="checkpoint path to restore from: a train-state "
                    "snapshot resumes the run at the saved iteration "
                    "(exact replay); a legacy params-only checkpoint "
                    "restores just the policy weights")
    ap.add_argument("--fault-plan", default=None, metavar="SPEC",
                    help="deterministic fault injection, e.g. "
                    "'stage.ref_inference@1,swap.in@2,dock.put@3:fatal' — "
                    "site@hit[:kind] entries; see docs/resilience.md")
    args = ap.parse_args()
    if args.partial_rollout and args.algorithm == "ppo":
        ap.error("--partial-rollout implements the GRPO family; "
                 "it cannot be combined with --algorithm ppo")

    # imports deferred so --help never initializes jax
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    from repro.checkpoint import (is_train_state, load_pytree,
                                  load_train_state, save_pytree,
                                  save_train_state)
    from repro.core.partial import PartialRolloutTrainer
    from repro.core.ppo_trainer import PPOTrainer
    from repro.core.trainer import GRPOTrainer
    from repro.data.prompts import PromptDataset, arithmetic_task, pattern_task
    from repro.resilience import FatalFault, FaultPlan

    if args.checkpoint_every and not args.checkpoint:
        ap.error("--checkpoint-every needs --checkpoint PATH")
    faults = FaultPlan.parse(args.fault_plan) if args.fault_plan else None

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.smoke:
        cfg = cfg.replace(dtype="float32", remat=False)
    rl = RLConfig(
        algorithm=args.algorithm,
        num_generations=args.num_generations,
        max_prompt_len=args.max_prompt_len,
        max_response_len=args.max_response_len,
        lr=args.lr, kl_coef=args.kl_coef,
        temperature=args.temperature,
        clip_eps=args.clip_eps,
        use_transfer_dock=not args.no_transfer_dock,
        use_allgather_swap=not args.no_allgather_swap,
        stage_fusion=not args.no_stage_fusion,
        partial_rollout=args.partial_rollout,
        num_warehouses=args.num_nodes,
        serve_prefix_cache=not args.no_prefix_cache,
        serve_prefill_chunk=args.prefill_chunk,
        serve_host_tier_blocks=args.host_tier_blocks,
        serve_sampling_seed=args.serve_sampling_seed,
        serve_top_p=args.serve_top_p,
        serve_top_k=args.serve_top_k,
    )
    if args.rollout_engine:
        rl = rl.replace(rollout_engine=args.rollout_engine)
    if args.serve_max_slots:
        rl = rl.replace(serve_max_slots=args.serve_max_slots)
    if args.serve_block_size:
        rl = rl.replace(serve_block_size=args.serve_block_size)
    if args.trace:
        rl = rl.replace(trace_path=args.trace)
    if args.print_graph:
        # static declaration — no model/optimizer init needed; node ids
        # match the trainer's worker placement for --num-nodes
        from repro.core.partial import build_partial_graph
        from repro.core.ppo_trainer import build_ppo_graph
        from repro.core.trainer import build_grpo_graph
        build = (build_partial_graph if args.partial_rollout
                 else build_ppo_graph if args.algorithm == "ppo"
                 else build_grpo_graph)
        print(build(0, 1 % args.num_nodes, 2 % args.num_nodes).describe())
        return

    task = pattern_task() if args.task == "pattern" else arithmetic_task()
    ds = PromptDataset(task, max_prompt_len=rl.max_prompt_len, seed=args.seed)
    # every algorithm is a graph DECLARATION over the same executor: the
    # trainer classes differ only in which RLGraph they build
    if args.partial_rollout:
        trainer = PartialRolloutTrainer(cfg, rl, ds, budget=args.rollout_budget,
                                        num_nodes=args.num_nodes,
                                        seed=args.seed, faults=faults)
    elif args.algorithm == "ppo":
        trainer = PPOTrainer(cfg, rl, ds, num_nodes=args.num_nodes,
                             seed=args.seed, faults=faults)
    else:
        trainer = GRPOTrainer(cfg, rl, ds, num_nodes=args.num_nodes,
                              seed=args.seed, faults=faults)
    start = 0
    if args.resume:
        if is_train_state(args.resume):
            start = load_train_state(args.resume, trainer)
            print(f"resumed train state from {args.resume} "
                  f"(iteration {start})")
        else:
            trainer.params = load_pytree(args.resume, trainer.params)
            print(f"restored policy from {args.resume}")

    log = []
    for it in range(start, args.iterations):
        t0 = time.perf_counter()
        try:
            st = trainer.iteration(args.global_batch)
        except FatalFault as err:
            # injected unrecoverable fault (chaos testing): flush what we
            # have so a --resume run can be compared against the log, then
            # exit with a distinct status the CI smoke asserts on
            print(f"fatal injected fault: {err}")
            if args.log_json:
                with open(args.log_json, "w") as f:
                    json.dump(log, f, indent=1)
            raise SystemExit(3)
        tput = trainer.throughput(st, args.global_batch)
        rec = {
            "iteration": it, "reward": st.reward_mean, "loss": st.loss,
            "kl": st.kl, "tokens_per_s_per_device": tput,
            "ete_s": time.perf_counter() - t0,
            "dispatch_s": st.dispatch["simulated_dispatch_time_s"],
            "reshard_swap_s": st.reshard.get("modeled_swap_time_s", 0.0),
        }
        log.append(rec)
        print(f"[{it:4d}] reward={st.reward_mean:6.3f} loss={st.loss:8.4f} "
              f"kl={st.kl:.5f} T={tput:8.1f} tok/s/dev "
              f"ete={rec['ete_s']:6.2f}s")
        if args.checkpoint_every and (it + 1) % args.checkpoint_every == 0:
            save_train_state(args.checkpoint, trainer, iteration=it + 1)

    if args.log_json:
        with open(args.log_json, "w") as f:
            json.dump(log, f, indent=1)
    if args.trace:
        print(f"wrote trace to {trainer.export_trace()} "
              f"(open at https://ui.perfetto.dev)")
    if args.checkpoint:
        if args.checkpoint_every:
            save_train_state(args.checkpoint, trainer,
                             iteration=args.iterations)
        else:
            save_pytree(args.checkpoint, trainer.params, step=args.iterations)
        print(f"saved checkpoint to {args.checkpoint}")


if __name__ == "__main__":
    main()
