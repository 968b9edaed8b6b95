"""Build (step_fn, shapes, shardings) for every (arch x input-shape x mesh).

This is the single source of truth used by dryrun.py (lower+compile),
train.py (real training) and serve.py. Everything is built from
ShapeDtypeStructs — no device allocation happens here.

Program kinds (from ShapeConfig.kind):
  train   -> one FL round: replicated-client cohort step or
             distributed-client streaming step (ArchConfig.fl_mode)
  prefill -> model.prefill_logits over the full prompt
  decode  -> model.decode_step: ONE new token against a seq_len KV cache
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import INPUT_SHAPES, ArchConfig, FLConfig, ModelConfig
from repro.configs.registry import get_arch
from repro.core.cohort import (
    init_cohort_state,
    init_dist_state,
    make_cohort_step,
    make_dist_step,
)
from repro.launch.mesh import batch_axes_for
from repro.models.model import build_model
from repro.sharding.specs import (
    batch_pspecs,
    cache_pspecs,
    cohort_state_pspecs,
    dist_state_pspecs,
    param_pspecs,
)

# Dry-run FL hyper-parameters: M=2 local steps keeps the round FLOPs at
# 2x(fwd+bwd) per slot; K/arrivals chosen per cohort size at build time.
DRYRUN_FL = FLConfig(local_steps=2, local_lr=1e-2, weighting="paper")
PROBE_BATCH = 4


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(int(s) for s in shape), jnp.dtype(dtype))


def _model_batch_sds(cfg: ModelConfig, lead: Tuple[int, ...], seq: int,
                     with_labels: bool = True) -> Dict[str, Any]:
    """Batch leaves with arbitrary leading dims + a seq dim."""
    emb_dtype = jnp.dtype(cfg.compute_dtype)
    text = seq
    batch: Dict[str, Any] = {}
    if cfg.num_patches:
        text = seq - cfg.num_patches
        batch["patches"] = _sds(lead + (cfg.num_patches, cfg.d_model), emb_dtype)
    if cfg.is_encdec:
        batch["frames"] = _sds(lead + (cfg.encoder_seq_len, cfg.d_model), emb_dtype)
    batch["tokens"] = _sds(lead + (text,), jnp.int32)
    if with_labels:
        batch["labels"] = _sds(lead + (text,), jnp.int32)
    return batch


@dataclasses.dataclass
class Program:
    name: str
    kind: str
    step_fn: Callable
    arg_sds: Tuple[Any, ...]  # ShapeDtypeStructs, positional
    in_shardings: Tuple[Any, ...]
    out_shardings: Any
    static_argnums: Tuple[int, ...] = ()
    donate_argnums: Tuple[int, ...] = ()
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)


def _named(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def resolve_model_cfg(arch: ArchConfig, shape_name: str) -> ModelConfig:
    """Apply per-shape variants (long_500k -> sliding-window for dense)."""
    cfg = arch.model
    if shape_name == "long_500k" and arch.long_context_window and not (
            cfg.attn_window or cfg.is_ssm_only):
        cfg = cfg.replace(attn_window=arch.long_context_window)
    return cfg


def build_program(arch_id: str, shape_name: str, mesh,
                  fl: Optional[FLConfig] = None,
                  model_overrides: Optional[Dict[str, Any]] = None) -> Program:
    arch = get_arch(arch_id)
    shape = INPUT_SHAPES[shape_name]
    if shape_name in arch.skip_shapes:
        raise ValueError(f"{arch_id} skips {shape_name}: see DESIGN.md")
    cfg = resolve_model_cfg(arch, shape_name)
    if model_overrides:
        cfg = cfg.replace(**model_overrides)
    model = build_model(cfg)
    baxes = batch_axes_for(mesh)
    dp = int(np.prod([dict(zip(mesh.axis_names, mesh.devices.shape))[a]
                      for a in baxes]))
    fl = fl or DRYRUN_FL
    params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    meta = {"arch": arch_id, "shape": shape_name,
            "mesh": dict(zip(mesh.axis_names, mesh.devices.shape)),
            "params": int(sum(np.prod(l.shape) for l in jax.tree.leaves(params_sds))),
            "active_params": model.active_param_count(params_sds),
            "fl_mode": arch.fl_mode, "seq_len": shape.seq_len,
            "global_batch": shape.global_batch}

    if shape.kind == "train":
        if arch.fl_mode == "replicated":
            return _build_cohort_train(model, fl, shape, mesh, baxes, dp, meta)
        return _build_dist_train(model, fl, shape, mesh, baxes, dp, meta)
    if shape.kind == "prefill":
        return _build_prefill(model, shape, arch, mesh, baxes, dp, meta)
    return _build_decode(model, shape, arch, mesh, baxes, dp, meta)


# ---------------------------------------------------------------------------


def _build_cohort_train(model, fl, shape, mesh, baxes, dp, meta) -> Program:
    cfg = model.cfg
    cohort = dp  # one client slot per data-parallel group
    assert shape.global_batch % cohort == 0, (shape.global_batch, cohort)
    b = shape.global_batch // cohort
    m = fl.local_steps
    state_sds = jax.eval_shape(lambda p: init_cohort_state(p, cohort),
                               jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    batch_sds = {
        "local": _model_batch_sds(cfg, (cohort, m, b), shape.seq_len),
        "probe": _model_batch_sds(cfg, (cohort, PROBE_BATCH), shape.seq_len),
        "arrival": _sds((cohort,), jnp.float32),
        "data_sizes": _sds((cohort,), jnp.float32),
    }
    state_specs = cohort_state_pspecs(state_sds, mesh, client_axes=baxes)
    batch_specs = batch_pspecs(batch_sds, batch_axes=baxes)
    # the round substrate shards explicitly on this mesh (DESIGN.md §5):
    # C-slot vmap over data, flat-vector server pass over model
    step = make_cohort_step(model.loss, fl, mesh=mesh)
    metrics_specs = {"fresh_loss_mean": P(), "staleness_min": P(),
                     "weights_max": P(), "update_sq_norm": P()}
    meta.update(cohort=cohort, local_batch=b, local_steps=m)
    return Program(
        name=f"{meta['arch']}:{meta['shape']}", kind="train", step_fn=step,
        arg_sds=(state_sds, batch_sds),
        in_shardings=(_named(mesh, state_specs), _named(mesh, batch_specs)),
        out_shardings=(_named(mesh, state_specs), _named(mesh, metrics_specs)),
        donate_argnums=(0,), meta=meta)


def _build_dist_train(model, fl, shape, mesh, baxes, dp, meta) -> Program:
    cfg = model.cfg
    m = fl.local_steps
    state_sds = jax.eval_shape(
        lambda p: init_dist_state(p, fl),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    batch_sds = {
        "local": _model_batch_sds(cfg, (m, shape.global_batch), shape.seq_len),
        "probe": _model_batch_sds(cfg, (fl.probe_batch * dp,), shape.seq_len),
        "tau": _sds((), jnp.int32),
        "data_size": _sds((), jnp.float32),
    }
    state_specs = dist_state_pspecs(state_sds, mesh)

    def bspec(l):
        if l.ndim == 0:
            return P()
        if l.ndim >= 2:  # (M, b, ...): shard b
            ax = baxes if len(baxes) > 1 else baxes[0]
            return P(None, ax, *([None] * (l.ndim - 2)))
        return P()

    batch_specs = {
        "local": jax.tree.map(bspec, batch_sds["local"]),
        "probe": batch_pspecs(batch_sds["probe"], batch_axes=baxes),
        "tau": P(), "data_size": P(),
    }
    step = make_dist_step(model.loss, fl)
    metrics_specs = P()  # every metric replicated (a prefix of the tree)
    meta.update(cohort=1, local_batch=shape.global_batch, local_steps=m)
    return Program(
        name=f"{meta['arch']}:{meta['shape']}", kind="train", step_fn=step,
        arg_sds=(state_sds, batch_sds),
        in_shardings=(_named(mesh, state_specs), _named(mesh, batch_specs)),
        out_shardings=(_named(mesh, state_specs), _named(mesh, metrics_specs)),
        donate_argnums=(0,), meta=meta)


def make_io_hooks(*, ckpt_path: Optional[str] = None, ckpt_every: int = 0,
                  log_fn: Callable[[str], None] = print,
                  registry: Optional[Any] = None,
                  tracer: Optional[Any] = None,
                  sink: Optional[Any] = None):
    """Coordinator-gated IO for multi-controller training loops (§7),
    reporting into the observability plane (§9).

    Returns ``(log, eval_metrics, maybe_save)``:

    * ``log(msg)`` — emits only on process 0 (every process may call it);
      with a ``sink`` (obs.JsonlSink / InMemorySink) each message is also
      emitted as a structured ``{"event": "log", ...}`` record (the sink
      applies its own coordinator gate);
    * ``eval_metrics(metrics)`` — fetches a metrics pytree to host floats
      from process-local addressable shards (ALL processes must call it:
      non-replicated leaves cost one resharding collective), returning
      the dict everywhere so control flow stays identical across
      processes; the fetch is a ``host_sync`` span and every metric lands
      in a ``train_<name>`` registry gauge;
    * ``maybe_save(step, tree)`` — writes ``ckpt_path`` every
      ``ckpt_every`` steps via the coordinator-gated
      ``checkpoint.save_checkpoint`` (again: call on every process),
      timed as a ``checkpoint`` span and counted in the registry.

    Keeping the gate in ONE place means a training loop written against
    these hooks runs unchanged on a laptop and on a pod slice.
    """
    from repro.checkpoint import save_checkpoint
    from repro.launch.multihost import fetch_replicated, is_coordinator
    from repro.obs.metrics import default_registry
    from repro.obs.trace import NULL_TRACER, SPAN_CHECKPOINT, SPAN_HOST_SYNC

    reg = registry if registry is not None else default_registry()
    tr = tracer if tracer is not None else NULL_TRACER
    syncs = reg.counter("train_host_syncs_total")
    ckpts = reg.counter("train_checkpoints_total")

    def log(msg: str) -> None:
        if sink is not None:
            sink.emit({"event": "log", "msg": msg})
        if is_coordinator():
            log_fn(msg)

    def eval_metrics(metrics: Any) -> Dict[str, float]:
        with tr.span(SPAN_HOST_SYNC, what="eval_metrics"):
            syncs.inc()
            host = fetch_replicated(metrics)
        out = {k: float(np.asarray(v)) for k, v in host.items()}
        for k, v in out.items():
            reg.gauge(f"train_{k}").set(v)
        return out

    def maybe_save(step: int, tree: Any) -> bool:
        if not ckpt_path or not ckpt_every or step % ckpt_every:
            return False
        with tr.span(SPAN_CHECKPOINT, step=step):
            ckpts.inc()
            save_checkpoint(ckpt_path, tree, step=step)
        return True

    return log, eval_metrics, maybe_save


def _build_prefill(model, shape, arch, mesh, baxes, dp, meta) -> Program:
    cfg = model.cfg
    fsdp = arch.fl_mode == "distributed"
    params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    batch_sds = _model_batch_sds(cfg, (shape.global_batch,), shape.seq_len,
                                 with_labels=False)
    pspecs = param_pspecs(params_sds, mesh, fsdp=fsdp)
    bspecs = batch_pspecs(batch_sds, batch_axes=baxes)

    def step(params, batch):
        return model.prefill_logits(params, batch)

    return Program(
        name=f"{meta['arch']}:{meta['shape']}", kind="prefill", step_fn=step,
        arg_sds=(params_sds, batch_sds),
        in_shardings=(_named(mesh, pspecs), _named(mesh, bspecs)),
        out_shardings=None, meta=meta)


def _build_decode(model, shape, arch, mesh, baxes, dp, meta) -> Program:
    cfg = model.cfg
    fsdp = arch.fl_mode == "distributed"
    b = shape.global_batch
    params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache_sds = jax.eval_shape(lambda: model.init_cache(b, shape.seq_len))
    tok_sds = _sds((b, 1), jnp.int32)
    pspecs = param_pspecs(params_sds, mesh, fsdp=fsdp)
    cspecs = cache_pspecs(cache_sds, mesh, batch_axes=baxes)
    if b % dp == 0:
        tok_spec = P(baxes if len(baxes) > 1 else baxes[0], None)
    else:
        tok_spec = P(None, None)  # e.g. long_500k: batch=1 cannot shard

    def step(params, cache, tokens, pos):
        return model.decode_step(params, cache, tokens, pos)

    meta.update(cache_len=min(shape.seq_len, cfg.attn_window or shape.seq_len))
    return Program(
        name=f"{meta['arch']}:{meta['shape']}", kind="decode", step_fn=step,
        arg_sds=(params_sds, cache_sds, tok_sds, _sds((), jnp.int32)),
        in_shardings=(_named(mesh, pspecs), _named(mesh, cspecs),
                      NamedSharding(mesh, tok_spec),
                      NamedSharding(mesh, P())),
        out_shardings=None, donate_argnums=(1,), meta=meta)
