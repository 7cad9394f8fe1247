"""Training loop: Eq.-16 loss, joint weight+bitwidth optimization, Pareto
checkpointing, fault-tolerant resume.

``make_train_step`` builds the pure step function (pjit-able — the launcher
wraps it with shardings); :class:`Trainer` is the host-side driver with
checkpoint/restart and the paper's beta-ramp Pareto sweep.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core import hgq
from ..core.pareto import ParetoFront
from ..core.schedule import Schedule, constant, log_ramp
from ..dist import collectives
from ..optim import AdamWState, adamw_init, adamw_update, clip_by_global_norm
from . import checkpoint as ckpt_lib

Forward = Callable[..., Tuple[jax.Array, Any, Any]]
LossFn = Callable[[jax.Array, Dict[str, jax.Array]], jax.Array]


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    steps: int = 1000
    lr: float = 1e-3
    weight_decay: float = 0.0
    clip_norm: float = 1.0
    beta0: float = 1e-6          # Eq. 16 resource coefficient (ramped)
    beta1: float = 1e-4
    gamma: float = 2e-6          # Eq. 16 L1 coefficient (paper: fixed 2e-6)
    beta_const: Optional[float] = None  # HGQ-c* variant: fixed beta
    log_every: int = 50
    eval_every: int = 100
    ckpt_every: int = 200
    ckpt_dir: str = ""
    keep_ckpts: int = 3


def _merge_sliced_qstate(newqs):
    """Reconcile the per-slice activation-range states a vmapped forward
    returns ([n_slices, ...] leaves) back into one qstate: extremes merge
    with min/max over the slice axis — identical to what the unsliced
    forward would have observed on the full batch."""
    def merge(node):
        if isinstance(node, hgq.ActState):
            return hgq.ActState(vmin=jnp.min(node.vmin, axis=0),
                                vmax=jnp.max(node.vmax, axis=0))
        return jnp.mean(node, axis=0)
    return jax.tree.map(merge, newqs,
                        is_leaf=lambda x: isinstance(x, hgq.ActState))


def make_train_step(forward: Forward, loss_fn: LossFn, tcfg: TrainConfig,
                    lr_sched: Optional[Schedule] = None,
                    grad_tx: Optional[Callable] = None,
                    reduce: str = "full", mesh=None,
                    wire_kind: str = "int8", wire_layout: str = "auto",
                    wire_widths: Optional[Any] = None,
                    wire_fused: bool = True):
    """Build the pure train step.

    With ``grad_tx`` (e.g. ``dist.ef_compress`` partial application: a
    ``(grads, state) -> (grads, state)`` transform applied after clipping),
    the step takes and returns one extra ``tx_state`` argument so the
    error-feedback residual threads through pjit.

    ``reduce="compressed"`` moves the compression *into* the data-parallel
    reduction: per-shard gradients come from a vmap over ``n_data`` batch
    slices (sharded on the slice axis, so no fp32 gradient collective is
    ever emitted) and are mean-reduced by the int8-on-the-wire collective
    under ``mesh``.  ``wire_layout`` picks the exchange topology:

    * ``"1d"`` — ``collectives.ef_wire_pmean``: two-phase exchange over
      the data axes only; every model (TP) shard reduces the full
      gradient.  ``tx_state`` carries a leading ``[n_data]`` residual
      (``collectives.ef_wire_init``; shard with
      ``sharding.ef_residual_sharding``).
    * ``"2d"`` — ``collectives.ef_wire_pmean_2d``: each (data, model)
      device reduces only its 1/(D*M) slice, and one int8 all_gather over
      ``model`` rematerializes the full gradient.  ``tx_state`` carries
      the sliced ``[n_data, n_model, C]`` residual
      (``collectives.ef_wire2d_init``; shard with
      ``sharding.ef_residual_sharding(..., layout="2d")``).
    * ``"auto"`` — ``"2d"`` when ``mesh`` has a model axis of size > 1,
      else ``"1d"``.

    ``wire_widths`` (a ``core.plan.PrecisionPlan``) selects per-leaf wire
    widths for the compressed reduction — its ``wire_bits_tree`` over the
    gradient tree feeds the collective's ``widths`` argument.  ``None``
    (or a uniform-int8 plan, which callers normalize to ``None``) traces
    the exact legacy int8 wire.  ``wire_fused`` (default) selects the
    fused/pipelined wire fast path (``CompressionSpec.fused``) — the
    delivered values are bit-for-bit the per-leaf trace either way.

    Global-norm clipping applies to the *delivered* mean gradient
    (post-reduce compression clips before — the true pre-reduce global
    norm is unknowable without the very fp32 reduce this path removes).
    With ``mesh=None``, or a single device, the compressed path
    degenerates to the post-reduce ``ef_compress(kind=wire_kind)``
    transform, bit-for-bit.
    """
    if reduce not in ("full", "compressed"):
        raise ValueError(f"reduce must be 'full' or 'compressed', "
                         f"got {reduce!r}")
    if wire_layout not in ("auto", "1d", "2d"):
        raise ValueError(f"wire_layout must be 'auto', '1d' or '2d', "
                         f"got {wire_layout!r}")
    beta_sched = (constant(tcfg.beta_const) if tcfg.beta_const is not None
                  else log_ramp(tcfg.beta0, tcfg.beta1, tcfg.steps))
    lr_sched = lr_sched or constant(tcfg.lr)

    if reduce == "compressed":
        if grad_tx is not None:
            raise ValueError(
                "grad_tx and reduce='compressed' are mutually exclusive: "
                "the compressed reduction IS the gradient transform "
                "(wire_kind selects its quantization)")
        n_data = collectives.data_axis_size(mesh) if mesh is not None else 1
        n_model = (collectives.model_axis_size(mesh)
                   if mesh is not None else 1)
        if wire_layout == "auto":
            wire_layout = "2d" if n_model > 1 else "1d"
        if n_data <= 1 and not (wire_layout == "2d" and n_model > 1):
            # single device: the wire is a no-op — the current post-reduce
            # error-feedback path IS the compressed path, exactly
            from ..dist import ef_compress
            grad_tx = lambda g, s: ef_compress(g, s, kind=wire_kind)
        else:
            return _make_compressed_step(forward, loss_fn, tcfg, beta_sched,
                                         lr_sched, mesh, wire_kind, n_data,
                                         wire_layout, wire_widths,
                                         wire_fused)

    def _step(params, qstate, opt: AdamWState, batch, step, tx_state):
        beta = beta_sched(step)
        lr = lr_sched(step)

        def loss(params_):
            out, newq, aux = forward(params_, qstate, batch, mode=hgq.TRAIN)
            base = loss_fn(out, batch)
            total = base + beta * aux.ebops + tcfg.gamma * aux.l1
            return total, (newq, aux.ebops, base)

        (total, (newq, ebops, base)), grads = jax.value_and_grad(
            loss, has_aux=True)(params)
        grads, gnorm = clip_by_global_norm(grads, tcfg.clip_norm)
        if grad_tx is not None:
            grads, tx_state = grad_tx(grads, tx_state)
        params, opt = adamw_update(grads, opt, params, lr=lr,
                                   weight_decay=tcfg.weight_decay)
        metrics = {"loss": base, "total": total, "ebops": ebops,
                   "gnorm": gnorm, "beta": beta}
        return params, newq, opt, metrics, tx_state

    # every variant is named ``train_step``: jit names its program after
    # the function (``jit_train_step`` in a profile's module list)
    if grad_tx is None:
        def train_step(params, qstate, opt: AdamWState, batch, step):
            return _step(params, qstate, opt, batch, step, None)[:4]
        return train_step

    def train_step(params, qstate, opt: AdamWState, batch, step, tx_state):
        return _step(params, qstate, opt, batch, step, tx_state)
    return train_step


def _make_compressed_step(forward: Forward, loss_fn: LossFn,
                          tcfg: TrainConfig, beta_sched, lr_sched,
                          mesh, wire_kind: str, n_data: int,
                          wire_layout: str = "1d",
                          wire_widths: Optional[Any] = None,
                          wire_fused: bool = True):
    """The int8-on-the-wire train step (see ``make_train_step`` docstring).

    Per-shard gradients are materialized with a leading ``[n_data]`` axis
    (vmap of ``value_and_grad`` over equal batch slices, sharded over the
    data axes — the backward never sums across slices, so XLA emits no
    gradient all-reduce at all); the wire collective
    (``collectives.ef_wire_pmean`` / ``ef_wire_pmean_2d`` per
    ``wire_layout``) is then the only gradient communication in the
    program.
    """
    def train_step(params, qstate, opt: AdamWState, batch, step, tx_state):
        beta = beta_sched(step)
        lr = lr_sched(step)

        def loss_slice(params_, batch_slice):
            out, newq, aux = forward(params_, qstate, batch_slice,
                                     mode=hgq.TRAIN)
            base = loss_fn(out, batch_slice)
            total = base + beta * aux.ebops + tcfg.gamma * aux.l1
            return total, (newq, aux.ebops, base)

        def slice_leaf(b):
            if b.shape[0] % n_data:
                raise ValueError(
                    f"compressed reduce needs the batch axis ({b.shape[0]}) "
                    f"divisible by the {n_data} data shards")
            return b.reshape((n_data, b.shape[0] // n_data) + b.shape[1:])

        sliced = jax.tree.map(slice_leaf, batch)
        (totals, (newqs, ebops_s, bases)), grads = jax.vmap(
            jax.value_and_grad(loss_slice, has_aux=True),
            in_axes=(None, 0))(params, sliced)
        newq = _merge_sliced_qstate(newqs)
        # per-leaf wire widths from the PrecisionPlan (static ints keyed
        # by the grads tree paths; None = uniform int8, the legacy trace)
        widths = (None if wire_widths is None
                  else wire_widths.wire_bits_tree(grads))
        if wire_layout == "2d":
            # the residual lives in the sliced [n_data, n_model, C] layout,
            # so the grad+residual add happens on the slice, inside the
            # collective — gradients go in raw
            delivered, residual = collectives.ef_wire_pmean_2d(
                grads, tx_state.residual, mesh, wire_kind, widths=widths,
                fused=wire_fused)
        else:
            err = jax.tree.map(jnp.add, grads, tx_state.residual)
            delivered, residual = collectives.ef_wire_pmean(
                err, mesh, wire_kind, widths=widths, fused=wire_fused)
        delivered, gnorm = clip_by_global_norm(delivered, tcfg.clip_norm)
        params, opt = adamw_update(delivered, opt, params, lr=lr,
                                   weight_decay=tcfg.weight_decay)
        metrics = {"loss": jnp.mean(bases), "total": jnp.mean(totals),
                   "ebops": jnp.mean(ebops_s), "gnorm": gnorm, "beta": beta}
        return params, newq, opt, metrics, type(tx_state)(residual=residual)

    return train_step


class Trainer:
    """Host-side driver: jit, checkpoints, resume, Pareto tracking."""

    def __init__(self, forward: Forward, loss_fn: LossFn, tcfg: TrainConfig,
                 params, qstate, *,
                 eval_fn: Optional[Callable] = None,
                 pipeline: Optional[Callable[[int], Dict]] = None,
                 better_metric: str = "max",
                 grad_tx: Optional[Callable] = None,
                 tx_state: Optional[Any] = None):
        self.tcfg = tcfg
        self.forward = forward
        self.pipeline = pipeline
        self.eval_fn = eval_fn
        self.params = params
        self.qstate = qstate
        self.opt = adamw_init(params)
        self.start_step = 0
        self.pareto = ParetoFront(better_metric)
        # grad_tx must reach the jitted step — building the step without it
        # silently dropped any configured gradient compression (regression)
        self.grad_tx = grad_tx
        if grad_tx is not None:
            if tx_state is None:
                from ..dist import ef_init
                tx_state = ef_init(params)
            # the residual threads step-to-step like the optimizer state
            self.step_fn = jax.jit(
                make_train_step(forward, loss_fn, tcfg, grad_tx=grad_tx),
                donate_argnums=(0, 2, 5))
        else:
            if tx_state is not None:
                raise ValueError("tx_state given but no grad_tx transform; "
                                 "gradient compression would be silently "
                                 "ignored")
            self.step_fn = jax.jit(make_train_step(forward, loss_fn, tcfg),
                                   donate_argnums=(0, 2))
        self.tx_state = tx_state
        self.history = []

    # -------------------------- fault tolerance --------------------------
    def maybe_resume(self) -> bool:
        if not self.tcfg.ckpt_dir:
            return False
        last = ckpt_lib.latest_step(self.tcfg.ckpt_dir)
        if last is None:
            return False
        tmpl = {"params": self.params, "qstate": self.qstate, "opt": self.opt}
        # EF residual resumes rather than resetting — a zero residual would
        # bias the first post-resume window (only when the checkpoint has
        # one: a run may turn compression on mid-stream)
        if self.tx_state is not None and ckpt_lib.has_tree(
                self.tcfg.ckpt_dir, last, "ef"):
            tmpl["ef"] = self.tx_state
        _, trees = ckpt_lib.restore(self.tcfg.ckpt_dir, last, tmpl)
        self.params = trees["params"]
        self.qstate = trees["qstate"]
        self.opt = trees["opt"]
        self.tx_state = trees.get("ef", self.tx_state)
        self.start_step = last
        return True

    def checkpoint(self, step: int, pareto: bool = False) -> Optional[str]:
        if not self.tcfg.ckpt_dir:
            return None
        trees = {"params": self.params, "qstate": self.qstate,
                 "opt": self.opt}
        if self.tx_state is not None:
            trees["ef"] = self.tx_state
        path = ckpt_lib.save(self.tcfg.ckpt_dir, step, trees,
                             keep=self.tcfg.keep_ckpts)
        if pareto:
            ckpt_lib.mark_pareto(path)
        return path

    # ------------------------------- run ---------------------------------
    def run(self, steps: Optional[int] = None, log=print) -> Dict[str, Any]:
        tcfg = self.tcfg
        steps = steps or tcfg.steps
        t0 = time.time()
        m = {}
        for step in range(self.start_step, steps):
            batch = self.pipeline(step)
            if self.grad_tx is not None:
                (self.params, self.qstate, self.opt, m,
                 self.tx_state) = self.step_fn(
                    self.params, self.qstate, self.opt, batch,
                    jnp.int32(step), self.tx_state)
            else:
                self.params, self.qstate, self.opt, m = self.step_fn(
                    self.params, self.qstate, self.opt, batch,
                    jnp.int32(step))
            if step % tcfg.log_every == 0:
                mm = {k: float(v) for k, v in m.items()}
                log(f"step {step}: loss={mm['loss']:.4f} "
                    f"ebops={mm['ebops']:.3g} beta={mm['beta']:.2g}")
                self.history.append({"step": step, **mm})
            # checkpoint labels are "steps applied" (= next step to run):
            # after the step_fn above, that is step + 1 — labelling with
            # `step` would double-apply one batch on resume.  The Pareto
            # front records the same label so front entries map to their
            # pinned checkpoint directories.
            saved_pareto = False
            if self.eval_fn and step and step % tcfg.eval_every == 0:
                out = self.eval_fn(self.params, self.qstate)
                # eval_fn returns (metric, ebops) or (metric, ebops,
                # payload) — e.g. a core.plan.PrecisionPlan snapshot, so
                # every front point carries its deployable width table
                metric, ebops = out[0], out[1]
                payload = out[2] if len(out) > 2 else None
                if self.pareto.offer(metric, ebops, step + 1, payload):
                    path = self.checkpoint(step + 1, pareto=True)
                    saved_pareto = True
            if (tcfg.ckpt_dir and step and step % tcfg.ckpt_every == 0
                    and not saved_pareto):  # don't clobber the PARETO pin
                self.checkpoint(step + 1)
        return {"metrics": {k: float(v) for k, v in m.items()},
                "wall_s": time.time() - t0,
                "pareto": self.pareto.front()}
