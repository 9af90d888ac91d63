"""GRPO iterations of the program's ``GRPOTrainer``, back to back.

Traffic file keys: ``prompts_per_iteration`` (G), ``generations`` (N),
``prompt_len`` [lo, hi] (uniform, BOS included), ``prompt_pad``,
``response_len``, ``mesh`` (null, or the (data, model) shape over the
cell's chips), ``reference_iterations`` and ``rl`` (the trainer's
algorithm settings).  The task is the benchmark's own: a prompt is a
string of random letters, and a response's reward is the share of its
token ids that are even, so rewards differ inside every group and the
update has a gradient from the first step.

Set-up builds one trainer (weights from the seed, made on the device by
one compiled call), runs ``reference_iterations`` iterations through the
same ``iteration()`` call the window makes, recording what each produced,
and hands that trainer to the window.  The window runs iterations back to
back and ends with the first one that finishes after ``--seconds``.  After
it, the plain reference follows the recorded iterations (see
``reference/grpo.py``) and the check compares, by the widest gap and the
worst leaf, what the program produced with it.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

import check
import flops
import hostload
import program
import weights
from reference.grpo import GRPOReference, named, response_mask


def reward(ids) -> float:
    """Share of even token ids in a response (EOS excluded)."""
    ids = [int(i) for i in ids]
    return float(np.mean([i % 2 == 0 for i in ids])) if ids else 0.0


def prompt_lengths(t: dict) -> np.ndarray:
    """The G prompt lengths of every iteration: the uniform distribution's
    quantiles over ``prompt_len``, so that every iteration and every seed
    trains the same number of tokens (a seed permutes them)."""
    lo, hi = t["prompt_len"]
    g = t["prompts_per_iteration"]
    return np.rint(lo + (hi - lo) * (np.arange(g) + 0.5) / g).astype(int)


def make_task(t: dict):
    from repro.data.prompts import RuleTask

    lengths = prompt_lengths(t)
    state = {"i": 0, "perm": lengths}

    def make_prompt(rng):
        k = state["i"] % len(lengths)
        if k == 0:
            state["perm"] = rng.permutation(lengths)
        state["i"] += 1
        n = int(state["perm"][k]) - 1                   # BOS is one token
        return "".join(chr(97 + int(x)) for x in rng.integers(0, 26, n)), {}

    return RuleTask("even-ids", make_prompt,
                    lambda meta, text, ids: reward(ids))


def make_dataset(t: dict, seed: int):
    from repro.data.prompts import PromptDataset

    class Dataset(PromptDataset):
        """Records each draw's real prompt lengths (pads excluded)."""

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.lengths = []

        def sample(self, n):
            batch, lengths, metas = super().sample(n)
            self.lengths.append(np.asarray(lengths))
            return batch, lengths, metas

    return Dataset(make_task(t), max_prompt_len=t["prompt_pad"], seed=seed)


def make_trainer(c: dict, t: dict, seed: int, chips: int, trace: bool):
    from repro.configs.base import RLConfig
    from repro.core.trainer import GRPOTrainer
    from repro.launch.mesh import make_mesh
    from repro.obs import Tracer

    cfg = program.model_config(c)
    rl = RLConfig(num_generations=t["generations"],
                  max_prompt_len=t["prompt_pad"],
                  max_response_len=t["response_len"], **t["rl"])
    mesh = make_mesh(tuple(t["mesh"]), ("data", "model")) if t["mesh"] \
        else None
    if mesh is not None and mesh.size != chips:
        raise ValueError(f"mesh {t['mesh']} is not the cell's {chips} chips")

    class Trainer(GRPOTrainer):
        """The program's trainer with the benchmark's weights."""

        def _init_state(self, init, *args):
            super()._init_state(lambda k: weights.init(c, k),
                                weights.key(seed))

    tracer = Tracer(enabled=True) if trace else None
    return Trainer(cfg, rl, make_dataset(t, seed), seed=seed, mesh=mesh,
                   tracer=tracer), rl


def instrument(trainer, run, rec: dict) -> None:
    """Benchmark spans around the trainer's calls into each layer, and the
    records the check needs (``rec["keep"]`` set) or the window needs."""
    actor, ref, res = trainer.actor, trainer.ref, trainer.resharder
    generate, old_lp, ref_lp = actor.generate, actor.old_logprobs, \
        ref.logprobs
    step = trainer.train_step
    to_gen, to_upd = res.to_generation, res.to_update
    vocab = trainer.cfg.vocab_size
    faults = run.faults

    def gen_(*a, **kw):
        host = rec.get("host")
        m = host.mark() if host else None
        with run.span("generate"):
            out = generate(*a, **kw)
        if host:
            rec["gen_host"] = host.since(m)
        if rec["keep"] and "token" in faults:
            pl = out.tokens.shape[1] - out.gen_logp.shape[1]
            out.tokens[:, pl + 1] = (out.tokens[:, pl + 1] + 1) % vocab
        rec["lengths"].append(np.asarray(out.lengths))
        if rec["keep"]:
            rec["rolls"].append(out)
        return out

    def old_(*a, **kw):
        with run.span("old_logp"):
            return old_lp(*a, **kw)

    def ref_(*a, **kw):
        with run.span("ref_logp"):
            return ref_lp(*a, **kw)

    def step_(params, opt_state, batch):
        if rec["keep"]:
            rec["batches"].append(jax.device_get(batch))
            if "frozen" in faults:
                return params, opt_state, {"loss": jnp.float32(0.0),
                                           "kl": jnp.float32(0.0)}
            if "half_batch" in faults:
                half = batch["tokens"].shape[0] // 2
                batch = {k: v[:half] for k, v in batch.items()}
        with run.span("update"):
            return step(params, opt_state, batch)

    def to_gen_(*a, **kw):
        with run.span("reshard.to_generation"):
            return to_gen(*a, **kw)

    def to_upd_(*a, **kw):
        with run.span("reshard.to_update"):
            return to_upd(*a, **kw)

    actor.generate, actor.old_logprobs = gen_, old_
    ref.logprobs, trainer.train_step = ref_, step_
    res.to_generation, res.to_update = to_gen_, to_upd_


@jax.jit
def _first_grad_norms(mu, b1):
    """Adam's first moment after one step is (1 - b1) * g."""
    return jax.tree.map(lambda m: jnp.sqrt(jnp.sum(jnp.square(m / (1 - b1)))),
                        mu)


def drive(run, devices) -> dict:
    c, t = run.config, run.traffic
    dims = flops.Dims.from_config(c)
    G, N = t["prompts_per_iteration"], t["generations"]
    trainer, rl = make_trainer(c, t, run.seed, run.chips, run.trace)
    ds = trainer.dataset
    rec = {"keep": True, "rolls": [], "batches": [], "lengths": []}
    instrument(trainer, run, rec)

    # set-up: the first iterations, through the window's own call
    prog = {"loss": []}
    for k in range(t["reference_iterations"]):
        st = trainer.iteration(G)
        prog["loss"].append(st.loss)
        if k == 0:
            prog["grad_norms"] = named(_first_grad_norms(
                trainer.opt_state.mu, rl.betas[0]))
    prog["params"] = jax.device_get(trainer.params)
    rec["keep"] = False

    # the window
    its = []
    host = rec["host"] = hostload.HostLoad()
    h0 = host.mark()
    run.start_window()
    with run.traced():
        while True:
            hm = host.mark()
            t0 = time.perf_counter()
            with run.span("iteration"):
                st = trainer.iteration(G)
                jax.block_until_ready(trainer.params)
            wall = time.perf_counter() - t0
            plen = np.repeat(ds.lengths[-1], N)
            rlen = rec["lengths"][-1]
            its.append({
                "wall_s": wall,
                "tokens": int(plen.sum() + rlen.sum()),
                "flops": float(sum(flops.grpo_sample_flops(dims, int(p), int(r))
                                   for p, r in zip(plen, rlen))),
                "gen_s": st.gen_time, "infer_s": st.infer_time,
                "update_s": st.update_time,
                "reshard_s": st.reshard.get("wall_s", 0.0),
                "host": host.since(hm), "gen_host": rec.pop("gen_host"),
            })
            if time.perf_counter() - run.window_t0 >= run.seconds:
                break
    compiles = run.compiles_in_window()
    host_load = host.since(h0)
    host.close()
    g = trainer.executor.metrics
    failed = int(g.value("graph.quarantined"))
    window = {
        "kind": "grpo", "iterations": its,
        "attempted": len(its) * G * N, "failed": failed,
        "window_compiles": compiles, "host": host_load,
        "memory_peak_bytes": program.memory_peak_bytes(devices),
    }
    window["trace"] = run.reduce_trace(run.chips)

    # the check, once the program's state is freed
    rolls, batches = rec["rolls"], rec["batches"]
    eos = trainer.tok.eos_id
    pad_w = t["prompt_pad"]
    del trainer, rec, ds
    program.release()
    window["checks"] = compare(c, t, run, rl, rolls, batches, prog, eos,
                               pad_w, devices)
    return window


def _until(ids, eos):
    stop = np.nonzero(ids == eos)[0]
    return ids[:stop[0]] if len(stop) else ids


def iteration_data(batch: dict, n: int, pad_w: int, eos: int) -> dict:
    """What the reference needs of one recorded update batch: tokens,
    response mask and rewards, rows grouped by prompt (GRPO's groups)."""
    tokens = np.asarray(batch["tokens"])
    order = np.lexsort(tokens[:, :pad_w].T[::-1])
    tokens = tokens[order]
    mask = response_mask(tokens, pad_w, eos)
    rewards = np.asarray([reward(_until(row[pad_w:], eos)) for row in tokens],
                         np.float32)
    return {"tokens": tokens, "mask": mask, "rewards": rewards, "n": n,
            "order": order}


def compare(c, t, run, rl, rolls, batches, prog, eos, pad_w,
            devices) -> list:
    n = t["generations"]
    iters = [iteration_data(b, n, pad_w, eos) for b in batches]
    for it in iters:
        groups = it["tokens"][:, :pad_w].reshape(-1, n, pad_w)
        if not (groups == groups[:, :1]).all():
            raise RuntimeError("a recorded batch does not hold whole groups")
    mesh, shard = reference_placement(c, devices)
    make = jax.jit(lambda k: weights.init(c, k),
                   out_shardings=shard["weights"])
    w0_fn = lambda: make(weights.key(run.seed))  # noqa: E731
    if rl.algorithm != "grpo" or rl.entropy_coef:
        raise ValueError("the reference follows GRPO without an entropy "
                         "bonus; the traffic asks for something else")
    # the objective and optimizer as the cell states them (AdamW's eps is
    # the optimizer's default)
    rlx = {"kl_coef": rl.kl_coef, "clip_eps": rl.clip_eps, "lr": rl.lr,
           "betas": rl.betas, "eps": 1e-8, "weight_decay": rl.weight_decay,
           "grad_clip": rl.grad_clip}
    out = GRPOReference(c, rlx, shard=shard["data"]).run(w0_fn, iters)
    readings = readings_of(prog, out, iters, batches, rolls, pad_w, w0_fn)
    run.readings = readings
    if run.control:
        # the control: the reference in the program's place, in float8
        ctl = GRPOReference(c, rlx, quant="fp8",
                            shard=shard["data"]).run(w0_fn, iters)
        run.control_readings = control_readings(ctl, out, iters, pad_w)
    return check.held(readings, run.limits)


def readings_of(prog, out, iters, batches, rolls, pad_w, w0_fn) -> dict:
    gen_pairs, old_pairs, ref_pairs = [], [], []
    for k, (it, b) in enumerate(zip(iters, batches)):
        m = it["mask"][:, 1:] > 0
        o = it["order"]
        old_p = np.asarray(b["old_logp"])[o]
        ref_p = np.asarray(b["ref_logp"])[o]
        old_pairs.append((old_p[m], out["old_logp"][k][m]))
        ref_pairs.append((ref_p[m], out["ref_logp"][k][m]))
        by_row = {row.tobytes(): i for i, row in enumerate(it["tokens"])}
        r = rolls[k]
        for row, glp, ln in zip(r.tokens, r.gen_logp, r.lengths):
            i = by_row.get(np.asarray(row, np.int32).tobytes())
            ln = int(ln)
            if i is None:
                gen_pairs.append((np.zeros(1), np.full(1, np.inf)))
                continue
            gen_pairs.append((glp[:ln],
                              out["old_logp"][k][i, pad_w - 1:pad_w - 1 + ln]))
    prog_delta = _delta_norms(prog["params"], w0_fn)
    moving = check.moving_leaves(out["grad_norms"])
    return {
        "gen_logp": check.widest_gap(gen_pairs),
        "old_logp": check.widest_gap(old_pairs),
        "ref_logp": check.widest_gap(ref_pairs),
        "grad": check.worst_leaf_gap(prog["grad_norms"], out["grad_norms"]),
        "delta": check.worst_leaf_gap(prog_delta, out["delta_norms"], moving),
        "loss": max(abs(a - b) for a, b in zip(prog["loss"], out["loss"])),
    }


def control_readings(ctl, out, iters, pad_w) -> dict:
    """The same numbers for the control against the reference; its
    generation log-probabilities are its own forward's (old)."""
    pairs = {"old_logp": [], "ref_logp": [], "gen_logp": []}
    for k, it in enumerate(iters):
        m = it["mask"][:, 1:] > 0
        pairs["old_logp"].append((ctl["old_logp"][k][m],
                                  out["old_logp"][k][m]))
        pairs["ref_logp"].append((ctl["ref_logp"][k][m],
                                  out["ref_logp"][k][m]))
        resp = m[:, pad_w - 1:]
        pairs["gen_logp"].append((ctl["old_logp"][k][:, pad_w - 1:][resp],
                                  out["old_logp"][k][:, pad_w - 1:][resp]))
    moving = check.moving_leaves(out["grad_norms"])
    r = {n: check.widest_gap(p) for n, p in pairs.items()}
    r["grad"] = check.worst_leaf_gap(ctl["grad_norms"], out["grad_norms"])
    r["delta"] = check.worst_leaf_gap(ctl["delta_norms"], out["delta_norms"],
                                      moving)
    r["loss"] = max(abs(a - b) for a, b in zip(ctl["loss"], out["loss"]))
    return r


def _delta_norms(host_params, w0_fn) -> dict:
    from reference.grpo import leaf_diff_norms

    w0 = w0_fn()
    p = jax.tree.map(lambda h, d: jax.device_put(h, d.sharding),
                     host_params, w0)
    return leaf_diff_norms(p, w0)


def reference_placement(c: dict, devices):
    """On several chips the reference's weights are split along their
    largest divisible axis and its batches along rows; on one chip it runs
    unplaced."""
    if len(devices) == 1:
        return None, {"weights": None, "data": None}
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(devices), ("x",))
    nd = len(devices)

    def leaf_spec(shape):
        dims = sorted(range(len(shape)), key=lambda i: -shape[i])
        for i in dims:
            if shape[i] % nd == 0:
                spec = [None] * len(shape)
                spec[i] = "x"
                return NamedSharding(mesh, P(*spec))
        return NamedSharding(mesh, P())

    shapes = weights.shapes(c)
    wsh = jax.tree.map(leaf_spec, shapes,
                       is_leaf=lambda x: isinstance(x, tuple))

    def data(x):
        spec = P("x") if x.ndim and x.shape[0] % nd == 0 else P()
        return jax.device_put(x, NamedSharding(mesh, spec))

    return mesh, {"weights": wsh, "data": data}
