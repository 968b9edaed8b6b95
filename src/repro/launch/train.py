"""Training launcher: contribution-aware async FL rounds on a device mesh.

Runs REAL steps (allocates params), so it is meant for:
  * CPU/host smoke runs with reduced configs (--smoke), and
  * actual TPU slices with the full configs.

The arrival schedule (which cohort slots' uploads are buffered each round)
comes from the same heterogeneous latency model as the event-driven
simulator, so compiled training reproduces realistic staleness patterns.
Archs with ``fl_mode == "distributed"`` run the distributed-client mapping
instead: one client spanning the mesh folds its uploads one at a time
(``core/cohort.py::fold_upload``), each with the staleness the same
latency model gives it, and every K-th upload applies eq. 5.

Example:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \
      --shape train_4k --smoke --rounds 10
"""
from __future__ import annotations

import argparse
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import INPUT_SHAPES, FLConfig
from repro.configs.registry import get_arch
from repro.configs.base import smoke_variant
from repro.core.cohort import (
    fold_upload,
    init_cohort_state,
    init_dist_state,
    make_cohort_step,
    make_dist_step,
    staleness_stream,
)
from repro.core.simulator import LatencyModel
from repro.sim.scenarios import ClientBehavior
from repro.data.synthetic import make_lm_token_stream
from repro.launch.cli import (
    ObsStack,
    add_obs_flags,
    add_ring_codec_flag,
    add_seed_flag,
    enable_compile_cache,
)
from repro.launch.mesh import batch_axes_for, make_host_mesh
from repro.models.model import build_model


def arrival_schedule(num_slots: int, k: int, latency: LatencyModel,
                     rounds: int, seed: int = 0) -> np.ndarray:
    """(rounds, num_slots) 0/1 masks: the K slots with the earliest
    completion times arrive each round (straggler slots roll over)."""
    rng = np.random.default_rng(seed)
    remaining = np.array([latency.sample(rng, i) for i in range(num_slots)])
    out = np.zeros((rounds, num_slots), np.float32)
    for r in range(rounds):
        order = np.argsort(remaining)
        arrive = order[:k]
        out[r, arrive] = 1.0
        t = remaining[arrive].max()
        remaining = remaining - t
        for i in arrive:
            remaining[i] = latency.sample(rng, i)
    return out


def make_batches(cfg, cohort, m, b, bp, seq, rng):
    """Synthetic non-IID LM batches for one round (host-side pipeline)."""
    def toks(lead):
        n = int(np.prod(lead))
        t = make_lm_token_stream(cfg.vocab_size, seq, n, seed=int(rng.integers(1 << 30)))
        return t.reshape(*lead, seq + 1)

    text = seq - (cfg.num_patches or 0)
    local = toks((cohort, m, b))
    probe = toks((cohort, bp))
    batch = {
        "local": {"tokens": local[..., :text], "labels": local[..., 1:text + 1]},
        "probe": {"tokens": probe[..., :text], "labels": probe[..., 1:text + 1]},
    }
    if cfg.num_patches:
        batch["local"]["patches"] = rng.normal(
            size=(cohort, m, b, cfg.num_patches, cfg.d_model)).astype(np.float32)
        batch["probe"]["patches"] = rng.normal(
            size=(cohort, bp, cfg.num_patches, cfg.d_model)).astype(np.float32)
    if cfg.is_encdec:
        batch["local"]["frames"] = rng.normal(
            size=(cohort, m, b, cfg.encoder_seq_len, cfg.d_model)).astype(np.float32)
        batch["probe"]["frames"] = rng.normal(
            size=(cohort, bp, cfg.encoder_seq_len, cfg.d_model)).astype(np.float32)
    return batch


def run_distributed(model, fl, *, clients: int, rounds: int, seq: int,
                    batch: int, seed: int, log, obs) -> int:
    """``rounds`` server rounds of the distributed-client mapping: K
    uploads each, folded one after another, each acknowledged before the
    next. Returns the global version reached."""
    cfg = model.cfg
    rng = np.random.default_rng(seed)
    behavior = ClientBehavior.from_latency(
        LatencyModel.heterogeneous(clients, seed=seed), clients, seed)
    cids, taus = staleness_stream(behavior, fl.buffer_size,
                                  rounds * fl.buffer_size)
    sizes = rng.integers(500, 2000, clients).astype(np.float32)
    state = init_dist_state(model.init(jax.random.PRNGKey(seed)), fl)
    step = jax.jit(make_dist_step(model.loss, fl), donate_argnums=0)
    tracer = obs.tracer if obs.trace_out else None
    for i, (cid, tau) in enumerate(zip(cids, taus)):
        upload = jax.tree.map(
            lambda a: a[0],
            make_batches(cfg, 1, fl.local_steps, batch, 1, seq, rng))
        upload.update(tau=np.int32(tau), data_size=sizes[cid])
        t0 = time.time()
        state, ack = fold_upload(step, state, upload, tracer=tracer,
                                 registry=obs.registry)
        log(f"upload {i + 1}: client={cid} tau={tau} "
            f"fresh_loss={ack['fresh_loss']:.4f} v={ack['v_weight']:.4g} "
            f"applied={int(ack['applied'])} ({time.time() - t0:.1f}s)")
        if ack["applied"]:
            obs.round_hook((i + 1) // fl.buffer_size)
    return int(state.version)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config + tiny shapes (CPU-runnable)")
    ap.add_argument("--cohort", type=int, default=4)
    ap.add_argument("--buffer-k", type=int, default=3)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--weighting", default="paper")
    add_seed_flag(ap)
    add_ring_codec_flag(
        ap, help_suffix=" — int8/delta shrink the R-deep version ring "
                        "for large models")
    ap.add_argument("--ckpt", default=None,
                    help="checkpoint path (coordinator-gated: only "
                         "process 0 writes)")
    ap.add_argument("--ckpt-every", type=int, default=5)
    # multi-host (DESIGN.md §7): same flags on every process
    ap.add_argument("--coordinator", default=None,
                    help="host:port of process 0 (enables jax.distributed)")
    ap.add_argument("--num-processes", type=int, default=1)
    ap.add_argument("--process-id", type=int, default=0)
    add_obs_flags(ap)
    args = ap.parse_args()
    enable_compile_cache()

    obs = ObsStack.from_args(args)

    if args.coordinator and args.num_processes > 1:
        from repro.launch.multihost import initialize
        initialize(args.coordinator, args.num_processes, args.process_id)

    arch = get_arch(args.arch)
    shape = INPUT_SHAPES[args.shape]
    cfg = smoke_variant(arch.model) if args.smoke else arch.model
    cohort = args.cohort if args.smoke else 16
    seq = args.seq if args.smoke else shape.seq_len
    b = args.batch if args.smoke else shape.global_batch // cohort
    fl = FLConfig(buffer_size=args.buffer_k, local_steps=2, local_lr=5e-3,
                  weighting=args.weighting, ring_codec=args.ring_codec)
    model = build_model(cfg)
    mesh = make_host_mesh()

    from repro.launch.program import make_io_hooks
    log, eval_metrics, maybe_save = make_io_hooks(
        ckpt_path=args.ckpt, ckpt_every=args.ckpt_every,
        log_fn=logging.getLogger("repro.launch.train").info,
        registry=obs.registry, tracer=obs.tracer, sink=obs.sink)
    if arch.fl_mode == "distributed":
        with mesh:
            version = run_distributed(model, fl, clients=cohort,
                                      rounds=args.rounds, seq=seq, batch=b,
                                      seed=args.seed, log=log, obs=obs)
        log(f"done; global version = {version}")
        obs.finish(args.rounds)
        return

    latency = LatencyModel.heterogeneous(cohort, seed=args.seed)
    sched = arrival_schedule(cohort, args.buffer_k, latency, args.rounds,
                             seed=args.seed)
    rng = np.random.default_rng(args.seed)
    params = model.init(jax.random.PRNGKey(args.seed))
    state = init_cohort_state(params, cohort)
    step = jax.jit(make_cohort_step(model.loss, fl), donate_argnums=0)
    sizes = jnp.asarray(rng.integers(500, 2000, cohort), jnp.float32)

    with mesh:
        for r in range(args.rounds):
            batch = make_batches(cfg, cohort, fl.local_steps, b, 2, seq, rng)
            batch = jax.tree.map(jnp.asarray, batch)
            batch["arrival"] = jnp.asarray(sched[r])
            batch["data_sizes"] = sizes
            t0 = time.time()
            state, mets = step(state, batch)
            mets = eval_metrics(mets)
            log(f"round {r + 1}: fresh_loss={mets['fresh_loss_mean']:.4f} "
                f"|u|^2={mets['update_sq_norm']:.3e} "
                f"arrivals={int(sched[r].sum())} ({time.time() - t0:.1f}s)")
            maybe_save(r + 1, {"params": state.global_params,
                               "version": state.version})
            obs.round_hook(r + 1)
    log(f"done; global version = {int(state.version)}")
    obs.finish(args.rounds)


if __name__ == "__main__":
    main()
