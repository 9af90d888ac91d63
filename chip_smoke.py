#!/usr/bin/env python3
"""Bring-up smoke run of the main path on TPU: GRPO training and serving at
yi-6b's published widths, through the entry points a user calls.

    python chip_smoke.py                # one chip: train + serve
    python chip_smoke.py --four-chips   # 2x2 mesh: sharded train + reshard

Configuration: ``get_config("yi-6b")`` with every published width unchanged
(d_model 4096, 32 query heads over 4 KV heads of head_dim 128, d_ff 11008,
vocabulary 64000, bf16, remat as configured) and only the depth cut: 2
layers on one chip, 4 across four.  All 32 layers hold about 6 B parameters,
and the trainer keeps the policy, the reference and Adam's f32 moments (14
to 16 bytes a parameter), which does not fit one 16 GB v5e chip.  Weights
are random, made from ``--seed``.

One chip:
  1. training — ``GRPOTrainer`` with its default configuration (sync
     rollout engine, transfer dock, allgather-swap, stage fusion) on the
     pattern task, 8 prompts x 4 generations, prompts <= 16 tokens and
     responses <= 48; 2 iterations (the first compiles).  Losses and KL
     must be finite and the weights must move.  On iteration 1 the
     generator's ``gen_logp`` and the update layout's ``old_logp`` come from
     the same weights and must agree on response tokens (``LOGP_TOL``).
  2. serving — ``ServingEngine`` (8 slots, block size 16) on the trained
     weights drains 8 requests of different lengths twice (the first drain
     compiles); every ``gen_logp`` must agree with a teacher-forced
     ``model.forward`` of the request's tokens (``LOGP_TOL``).
  The compiled update and serving steps must contain the Pallas kernels
  (``tpu_custom_call``), and no graph stage may have been retried or
  quarantined.

Four chips: a 2x2 ("data", "model") mesh given to ``GRPOTrainer(mesh=...)``;
the generation-layout weights and the weights swapped back from host memory
must be bitwise equal to the originals, the sharded forward's logits must
match the same forward on one device (``SHARDED_LOGIT_TOL``), and 2 training
iterations must run as above.

Everything runs in this one process, which holds the chip(s); it starts no
other.  It exits nonzero before any work when JAX finds no TPU.  Lines
before the last are bring-up observations, not benchmark numbers.  The last
line is one JSON object: ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# Tolerance on |gen_logp - teacher-forced logp| over response tokens, in
# nats.  Both sides run the same bf16 weights; they differ in where they
# round: the decode paths attend one token against a bf16 KV cache, the
# full forward runs the Pallas flash kernel over the whole sequence, and
# their matmuls accumulate in different orders.  bf16 keeps 8 significant
# bits, so a logit of magnitude |z| is rounded by up to |z| * 2**-9 at each
# such point (0.016 at |z| = 8); at smoke widths on the CPU this gave a
# largest gap of 0.023 and a mean of 0.005.  A fault (a wrong position,
# mask, cache row or weight layout) scores a token in another context,
# where a random model's logits are independent draws of unit scale: that
# moves logp by about one nat on average.  The mean bound sits 20x below
# that, the max bound 20x above the rounding seen so far.
LOGP_TOL = {"max": 0.5, "mean": 0.05}
# Tolerance on |sharded logits - single-device logits| (bf16 logits read as
# f32).  The sharded program adds partial products across the "model" axis
# in another order and rounds them to bf16 at other points, an ulp (2**-6
# at |z| = 2) here and there, compounded over four layers; at smoke widths
# on 4 virtual CPU devices the largest gap was 0.05 and the mean 0.0075.
# A wrong shard or gather makes logits independent, a mean gap near 1.
SHARDED_LOGIT_TOL = {"max": 0.5, "mean": 0.05}

GLOBAL_BATCH = 8          # prompts per iteration
NUM_GENERATIONS = 4       # responses per prompt
MAX_PROMPT_LEN = 16
MAX_RESPONSE_LEN = 48
SERVE_SLOTS = 8
SERVE_BLOCK = 16


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def observe(name: str, value) -> None:
    print(f"bring-up observation: {name} = {value}", flush=True)


def peak_bytes(device) -> int | None:
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def logp_gap(name: str, pairs) -> dict:
    """pairs: (engine logp, teacher-forced logp) arrays over response
    tokens.  Checks them against LOGP_TOL; returns the gap summary."""
    import numpy as np

    gap = np.concatenate([np.abs(np.asarray(a, np.float64)
                                 - np.asarray(b, np.float64))
                          for a, b in pairs])
    out = {"tokens": int(gap.size), "max": float(gap.max()),
           "mean": float(gap.mean())}
    observe(f"{name} |logp gap| (nats)", out)
    check(np.all(np.isfinite(gap)), f"{name}: non-finite logp")
    check(out["max"] <= LOGP_TOL["max"] and out["mean"] <= LOGP_TOL["mean"],
          f"{name}: logp gap {out} exceeds {LOGP_TOL}")
    return out


def require_kernels(name: str, compiled_text: str) -> None:
    check("tpu_custom_call" in compiled_text,
          f"compiled {name} holds no Pallas kernel (tpu_custom_call)")
    observe(f"{name} Pallas kernels",
            compiled_text.count("custom_call_target=\"tpu_custom_call\""))


# ---------------------------------------------------------------------------
# phase 1: training
# ---------------------------------------------------------------------------

def make_trainer(cfg, *, seed: int, mesh=None):
    """The default GRPOTrainer on the pattern task, 8 prompts x 4
    generations, prompts <= 16 tokens and responses <= 48."""
    import jax

    from repro.configs.base import RLConfig
    from repro.core.trainer import GRPOTrainer
    from repro.data.prompts import PromptDataset, pattern_task

    # A random model over a 64000-token vocabulary (practically) never
    # emits the task's byte tokens, so every reward and every GRPO advantage
    # is 0, and on iteration 1 the KL gradient is 0 as well: the update
    # would be exactly zero and prove nothing about the backward.  The
    # entropy bonus gives every weight a gradient.
    rl = RLConfig(num_generations=NUM_GENERATIONS,
                  max_prompt_len=MAX_PROMPT_LEN,
                  max_response_len=MAX_RESPONSE_LEN, entropy_coef=0.01)
    ds = PromptDataset(pattern_task(), max_prompt_len=rl.max_prompt_len,
                       seed=seed)
    t0 = time.perf_counter()
    trainer = GRPOTrainer(cfg, rl, ds, seed=seed, mesh=mesh)
    jax.block_until_ready(trainer.opt_state)
    observe("train setup s (init + placement)", time.perf_counter() - t0)
    observe("parameters",
            sum(x.size for x in jax.tree.leaves(trainer.params)))
    return trainer


def train_phase(trainer, *, on_chip: bool = True) -> None:
    """Runs 2 iterations and checks them (see the module docstring).
    ``on_chip=False`` skips only the check for Pallas kernels in the
    compiled programs, which a CPU run does not have."""
    import jax
    import numpy as np

    # record what the generator sampled and what the update layout scored,
    # to compare the two on iteration 1
    rollouts, scored, batches = [], [], []
    generate, old_logprobs = trainer.actor.generate, trainer.actor.old_logprobs
    train_step = trainer.train_step

    def recording_generate(*a, **kw):
        out = generate(*a, **kw)
        rollouts.append(out)
        return out

    def recording_old_logprobs(params, tokens, extras=None):
        out = old_logprobs(params, tokens, extras)
        scored.append((np.asarray(tokens), out))
        return out

    def recording_train_step(params, opt_state, batch):
        batches.append(batch)
        return train_step(params, opt_state, batch)

    trainer.actor.generate = recording_generate
    trainer.actor.old_logprobs = recording_old_logprobs
    trainer.train_step = recording_train_step

    before = jax.device_get(trainer.params)
    for it in range(2):
        t0 = time.perf_counter()
        st = trainer.iteration(GLOBAL_BATCH)
        jax.block_until_ready(trainer.params)
        dt = time.perf_counter() - t0
        observe(f"train iteration {it + 1} s "
                f"({'compile + run' if it == 0 else 'steady'})", dt)
        observe(f"train iteration {it + 1} loss, kl, reward",
                (st.loss, st.kl, st.reward_mean))
        observe(f"train iteration {it + 1} stage s (gen, infer, update, "
                f"reshard)", (st.gen_time, st.infer_time, st.update_time,
                              st.reshard["wall_s"]))
        check(np.isfinite(st.loss) and np.isfinite(st.kl),
              f"iteration {it + 1}: loss {st.loss} / kl {st.kl} not finite")
        if it == 0:
            plen = rollouts[0].tokens.shape[1] - MAX_RESPONSE_LEN
            by_row = {row.tobytes(): lp for toks, lps in scored
                      for row, lp in zip(toks, lps)}
            pairs = []
            for r in rollouts:
                for row, glp, n in zip(r.tokens, r.gen_logp, r.lengths):
                    olp = by_row[row.tobytes()]
                    pairs.append((glp[:n], olp[plen - 1:plen - 1 + n]))
            check(len(pairs) == GLOBAL_BATCH * NUM_GENERATIONS,
                  f"iteration 1 generated {len(pairs)} samples")
            logp_gap("train gen_logp vs old_logp", pairs)

    g = trainer.executor.metrics
    check(g.value("graph.retry") == 0 and g.value("graph.quarantined") == 0,
          f"graph retried {g.value('graph.retry')} / quarantined "
          f"{g.value('graph.quarantined')} stage samples")

    # every projection must move; norm scales start at 1.0, where a bf16
    # step (2**-7) dwarfs an lr-sized update, so they may not
    after = jax.tree.leaves(jax.device_get(trainer.params))
    changed = total = 0
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(before)[0],
                            after):
        moved = int(np.count_nonzero(a != b))
        check(path[-1].key == "scale" or moved > 0,
              f"weight {jax.tree_util.keystr(path)} did not change")
        changed += moved
        total += a.size
    observe("weight elements changed by 2 updates (fraction)",
            changed / total)

    # the update step as the trainer compiled it: under its mesh when the
    # mesh spans several devices (the executor sets it around each stage)
    t0 = time.perf_counter()
    with (jax.set_mesh(trainer.mesh) if trainer.mesh.size > 1
          else contextlib.nullcontext()):
        compiled = train_step.lower(trainer.params, trainer.opt_state,
                                    batches[-1]).compile()
    observe("update step re-lower + compile s", time.perf_counter() - t0)
    if on_chip:
        require_kernels("update step", compiled.as_text())
    trainer.train_step = train_step


# ---------------------------------------------------------------------------
# phase 2: serving
# ---------------------------------------------------------------------------

def serve_phase(cfg, params, *, seed: int, on_chip: bool = True) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.grpo import token_logprobs
    from repro.data.tokenizer import ByteTokenizer
    from repro.models.model import build_model
    from repro.serve.engine import ServingEngine

    tok = ByteTokenizer()
    engine = ServingEngine(cfg, max_new=MAX_RESPONSE_LEN, eos_id=tok.eos_id,
                           pad_id=tok.pad_id, max_slots=SERVE_SLOTS,
                           block_size=SERVE_BLOCK, seed=seed)
    model = build_model(cfg)
    width = MAX_PROMPT_LEN + MAX_RESPONSE_LEN

    @jax.jit
    def teacher_logp(params, tokens):
        logits, _ = model.forward(params, cfg, {"tokens": tokens})
        return token_logprobs(logits, tokens)

    rng = np.random.default_rng(seed)
    prompt_lens = (3, 5, 7, 9, 11, 13, 15, 16)
    max_news = (48, 40, 32, 24, 48, 16, 44, 36)
    for drain in range(2):
        for n, m in zip(prompt_lens, max_news):
            engine.submit(rng.integers(0, 256, size=n, dtype=np.int32),
                          max_new=m)
        t0 = time.perf_counter()
        outs = engine.drain(params)
        dt = time.perf_counter() - t0
        check(len(outs) == len(prompt_lens),
              f"drain {drain + 1} finished {len(outs)} of {len(prompt_lens)}")
        ntok = sum(len(o.gen) for o in outs)
        observe(f"serve drain {drain + 1} s "
                f"({'compile + run' if drain == 0 else 'steady'}), "
                f"generated tokens", (dt, ntok))
        tokens = np.full((len(outs), width), tok.pad_id, np.int32)
        for i, o in enumerate(outs):
            tokens[i, :len(o.tokens)] = o.tokens
        lp = np.asarray(teacher_logp(params, jnp.asarray(tokens)))
        pairs = [(o.gen_logp, lp[i, len(o.prompt) - 1:
                                 len(o.prompt) - 1 + len(o.gen)])
                 for i, o in enumerate(outs)]
        logp_gap(f"serve drain {drain + 1} gen_logp vs forward", pairs)

    s = SERVE_SLOTS
    t0 = time.perf_counter()
    compiled = engine._step.lower(
        params, engine.cache.pool_k, engine.cache.pool_v,
        jnp.asarray(engine.sched.tables), jnp.zeros((s, 1), jnp.int32),
        jnp.zeros((s,), jnp.int32), jnp.ones((s,), bool)).compile()
    observe("serving step re-lower + compile s", time.perf_counter() - t0)
    if on_chip:
        require_kernels("serving step", compiled.as_text())


# ---------------------------------------------------------------------------
# four chips: sharded training and the allgather-swap relayout
# ---------------------------------------------------------------------------

def four_chip_phase(cfg, *, seed: int, on_chip: bool = True) -> None:
    import jax
    import numpy as np

    from repro.launch.mesh import make_mesh
    from repro.models.model import build_model

    devices = jax.devices()
    check(len(devices) == 4, f"--four-chips needs 4 devices, "
                             f"JAX found {len(devices)}")
    mesh = make_mesh((2, 2), ("data", "model"))

    trainer = make_trainer(cfg, seed=seed, mesh=mesh)
    res = trainer.resharder

    def placed(tree, shardings):
        return jax.tree.all(jax.tree.map(
            lambda a, s: a.sharding.is_equivalent_to(s, a.ndim), tree,
            shardings))

    for name, tree in (("weights", trainer.params),
                       ("reference", trainer.ref_params),
                       ("Adam mu", trainer.opt_state.mu),
                       ("Adam nu", trainer.opt_state.nu)):
        check(placed(tree, res.train_shardings),
              f"{name} not placed with the train-stage shardings")

    def bits(tree):
        return [np.asarray(x).view(np.uint8) for x in jax.tree.leaves(tree)]

    orig = bits(jax.device_get(trainer.params))
    t0 = time.perf_counter()
    gen, stash, led = res.to_generation(trainer.params)
    jax.block_until_ready(gen)
    observe("to_generation s", time.perf_counter() - t0)
    check(placed(gen, res.gen_shardings),
          "generation weights not in the generation layout")
    check(all(np.array_equal(a, b) for a, b in zip(orig, bits(gen))),
          "generation-layout weights differ from the originals")
    del gen
    t0 = time.perf_counter()
    back, _ = res.to_update(stash, led)
    jax.block_until_ready(back)
    observe("to_update (H2D) s", time.perf_counter() - t0)
    check(placed(back, res.train_shardings),
          "weights swapped back are not in the update layout")
    check(all(np.array_equal(a, b) for a, b in zip(orig, bits(back))),
          "weights swapped back from host differ from the originals")
    del back, stash, orig
    observe("reshard relayout + host swap", "bitwise equal to originals")

    model = build_model(cfg)
    fwd = jax.jit(lambda p, t: model.forward(p, cfg, {"tokens": t})[0])
    tokens = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (8, MAX_PROMPT_LEN + MAX_RESPONSE_LEN),
        dtype=np.int32)
    with jax.set_mesh(mesh):     # as the trainer's stages run
        sharded = np.asarray(fwd(trainer.params, tokens), np.float32)
    one = jax.device_put(trainer.params, devices[0])
    single = np.asarray(fwd(one, tokens), np.float32)
    del one
    gap = np.abs(sharded - single)
    out = {"max": float(gap.max()), "mean": float(gap.mean()),
           "logit_absmax": float(np.abs(single).max())}
    observe("sharded vs single-device logits |gap|", out)
    check(np.all(np.isfinite(sharded)), "sharded logits not finite")
    check(out["max"] <= SHARDED_LOGIT_TOL["max"]
          and out["mean"] <= SHARDED_LOGIT_TOL["mean"],
          f"sharded logits gap {out} exceeds {SHARDED_LOGIT_TOL}")
    train_phase(trainer, on_chip=on_chip)


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2-mesh sharded training and "
                    "resharding checks (needs 4 chips)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2

    from repro.configs import get_config
    from repro.launch.cache import use_compile_cache

    observe("jax version", jax.__version__)
    observe("compile cache", use_compile_cache())
    observe("devices", f"{len(jax.devices())} x {dev.device_kind}")
    t_start = time.perf_counter()
    if args.four_chips:
        cfg = get_config("yi-6b").replace(num_layers=4)
        observe("config", "yi-6b published widths, depth cut 32 -> 4 layers")
        four_chip_phase(cfg, seed=args.seed)
        observe("peak bytes in use per device",
                [peak_bytes(d) for d in jax.devices()])
    else:
        cfg = get_config("yi-6b").replace(num_layers=2)
        observe("config", "yi-6b published widths, depth cut 32 -> 2 layers")
        trainer = make_trainer(cfg, seed=args.seed)
        train_phase(trainer)
        observe("peak bytes in use after training", peak_bytes(dev))
        serve_phase(cfg, trainer.params, seed=args.seed)
        observe("peak bytes in use after serving", peak_bytes(dev))
    observe("total s", time.perf_counter() - t_start)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
