"""RunContext: build a whole run — mesh, axis registry, shardings, train
step, serving engine — from a :class:`repro.api.RunSpec`, with **no
module-level mutable state**.

The old flow was ``set_axes(...)``; ``set_compute_dtype(...)``; build a
mesh by hand; wire ``make_train_step``/``Engine`` per launcher.  Every
jitted program silently depended on whatever those globals held when it
traced.  A :class:`RunContext` instead *carries* its configuration and
activates it as a dynamic scope (``dist.scope``) around every trace it
owns:

    ctx = repro.api.build(spec)
    setup = ctx.init_training()        # params/opt/EF state + jitted step
    setup.step(0)                      # shardings name ctx.mesh itself

    eng = ctx.make_engine(params, qstate)   # serving, same spec surface

Because nothing global is touched, two contexts with different
precision/axes coexist in one process — each keeps its own jit caches,
neither retraces nor perturbs the other (see ``tests/test_api.py``) —
which is what makes multi-tenant serving and side-by-side scenario
sweeps possible at all.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import AxisType

from .. import obs
from ..configs import get as get_config
from ..data import make_pipeline
from ..data.synthetic import DataSpec
from ..dist import EFState, collectives, ef_compress, ef_init
from ..dist.axes import AxisRegistry, axis_scope, registry_for_mesh
from ..dist.perf import compute_dtype_scope, packed_matmul
from ..dist.sharding import (batch_sharding, ef_residual_sharding,
                             replicated, shard_tree)
from ..models import model_for
from ..optim import adamw_init
from ..train import lm_loss, make_train_step
from ..train import checkpoint as ckpt_lib
from .spec import MeshSpec, RunSpec

_DTYPES = {None: None, "bfloat16": jnp.bfloat16, "float32": jnp.float32}


def build_mesh(mspec: MeshSpec):
    """Concrete ``jax.sharding.Mesh`` for a :class:`MeshSpec`.

    A function (never a module-level constant) so importing this module
    touches no jax device state — production meshes need the forced
    host-device XLA flag set before first jax init (``launch.dryrun``).
    Every axis is ``Auto``: the sharding code constrains activations
    and lets GSPMD place the rest (``jax.make_mesh`` defaults to
    ``Explicit`` axes, which reject such constraints).
    """
    return jax.make_mesh(mspec.shape, mspec.axis_names,
                         axis_types=(AxisType.Auto,) * len(mspec.shape))


@dataclasses.dataclass(frozen=True)
class GradCompression:
    """The resolved gradient-compression plan for one (spec, mesh) pair:
    either a post-reduce ``grad_tx`` transform or the in-reduction wire
    collective (``wire=True``), plus the initial EF state."""
    wire: bool
    wire_layout: str                  # "1d" | "2d" (resolved; wire only)
    reduce: str                       # "full" | "compressed"
    grad_tx: Optional[Callable]
    kind: str

    def init_state(self, params, n_data: int, n_model: int
                   ) -> Optional[EFState]:
        if self.kind == "none":
            return None
        if self.wire and self.wire_layout == "2d":
            return EFState(residual=collectives.ef_wire2d_init(
                params, n_data, n_model))
        if self.wire:
            return EFState(residual=collectives.ef_wire_init(
                params, n_data))
        return ef_init(params)


class TrainSetup:
    """Everything ``RunContext.init_training`` builds: state trees plus
    the jitted, sharding-annotated step.  ``step`` threads the optimizer
    and (when compression is on) EF residual state through itself."""

    def __init__(self, ctx: "RunContext", params, qstate, opt, ef_state,
                 jitted, pipeline):
        self.ctx = ctx
        self.params = params
        self.qstate = qstate
        self.opt = opt
        self.ef_state = ef_state
        self.jitted = jitted
        self.pipeline = pipeline
        self.start_step = 0

    def step(self, step: int) -> Dict[str, jax.Array]:
        with obs.span("train.step"):
            with obs.span("train.batch"):
                batch = self.pipeline(step)
            with obs.span("train.dispatch"):
                if self.ef_state is not None:
                    (self.params, self.qstate, self.opt, metrics,
                     self.ef_state) = self.jitted(
                        self.params, self.qstate, self.opt, batch,
                        jnp.int32(step), self.ef_state)
                else:
                    self.params, self.qstate, self.opt, metrics = \
                        self.jitted(self.params, self.qstate, self.opt,
                                    batch, jnp.int32(step))
        obs.count("train.steps")
        return metrics

    # --------------------- checkpointing / resume ----------------------

    def maybe_resume(self, log=print) -> bool:
        """Resume params/qstate/opt (and the EF residual, when present
        and shape-compatible) from the newest checkpoint."""
        ckpt_dir = self.ctx.spec.train.ckpt_dir
        if not ckpt_dir:
            return False
        last = ckpt_lib.latest_step(ckpt_dir)
        if last is None:
            return False
        tmpl = {"params": self.params, "qstate": self.qstate,
                "opt": self.opt}
        start, trees = ckpt_lib.restore(ckpt_dir, last, tmpl)
        self.params, self.qstate, self.opt = (
            trees["params"], trees["qstate"], trees["opt"])
        # EF residual resumes rather than resetting — but only when the
        # checkpoint has a shape-compatible one (a run may turn
        # compression on mid-stream, change kind, or rescale the mesh:
        # the 1D wire residual is [n_data, ...] and the 2D one
        # [n_data, n_model, C], so a rescale — or a 1d<->2d layout
        # switch — cannot re-chunk it: warn, restart it at zero, and eat
        # one biased window instead of dying)
        if self.ef_state is not None and ckpt_lib.has_tree(
                ckpt_dir, last, "ef"):
            try:
                _, eft = ckpt_lib.restore(ckpt_dir, last,
                                          {"ef": self.ef_state})
                self.ef_state = eft["ef"]
            except (AssertionError, KeyError):
                log("warning: checkpointed EF residual does not match "
                    "the current mesh/compression kind; restarting it "
                    "at zero")
        self.start_step = start
        return True

    def checkpoint(self, steps_applied: int) -> None:
        """Save under the 'steps applied' label (= next step to run)."""
        trees = {"params": self.params, "qstate": self.qstate,
                 "opt": self.opt}
        if self.ef_state is not None:
            trees["ef"] = self.ef_state
        ckpt_lib.save(self.ctx.spec.train.ckpt_dir, steps_applied, trees)


class RunContext:
    """A built run: the spec plus mesh, axis registry, resolved
    precision, and constructors for every derived object.  Cheap to
    build (no params are materialized until ``init_state`` /
    ``init_training``)."""

    def __init__(self, spec: RunSpec):
        self.spec = spec
        self.cfg = get_config(spec.arch, smoke=not spec.full)
        self.model = model_for(self.cfg)
        self.mesh = build_mesh(spec.mesh)
        self.axes: AxisRegistry = registry_for_mesh(self.mesh)
        self.compute_dtype = _DTYPES[spec.precision.compute_dtype]
        self.n_data = collectives.data_axis_size(self.mesh)
        self.n_model = collectives.model_axis_size(self.mesh)
        # effective precision plan: a missing plan and an explicit
        # uniform-int8 plan both resolve to None, so every consumer takes
        # the exact legacy (int8-everywhere) trace — spec files without
        # a plan stay HLO-byte-identical (tests/test_api.py)
        plan = spec.plan
        self.plan = None if plan is None or plan.is_uniform_int8 else plan
        # the un-normalized plan: kv_bits resolution must see every entry
        # (a plan that is uniform-int8 for wire/pack may still carry
        # narrow KV widths, and normalizing it away would drop them)
        self._full_plan = spec.plan

    # --------------------------- activation ----------------------------

    @contextlib.contextmanager
    def activate(self, packed: Optional[bool] = None):
        """Bind this context's trace-time configuration (axis registry,
        compute dtype, packed-kernel routing) for the enclosed block.
        Re-entrant and nestable across contexts; nothing global moves."""
        if packed is None:
            packed = self.spec.precision.packed_kernels
        with axis_scope(self.axes), \
                compute_dtype_scope(self.compute_dtype), \
                packed_matmul(packed):
            yield self

    def wrap(self, fn: Callable, packed: Optional[bool] = None) -> Callable:
        """Wrap ``fn`` so its *trace* runs under :meth:`activate` — the
        way every jitted function owned by this context is built.  (jit
        invokes the Python callable only on cache miss, so the scope is
        active exactly when trace-time flags are read.)"""
        @functools.wraps(fn)
        def traced(*args, **kwargs):
            with self.activate(packed=packed):
                return fn(*args, **kwargs)
        return traced

    # ------------------------- derived objects -------------------------

    @property
    def forward(self) -> Callable:
        cfg = self.cfg
        model = self.model
        return lambda p, q, b, mode: model.forward(p, q, b, cfg, mode)

    def data_spec(self) -> DataSpec:
        """The run's :class:`DataSpec` with vocab resolved from the
        architecture (a spec file may leave ``vocab=0``)."""
        ds = self.spec.data
        if ds.kind in ("lm", "asr") and ds.vocab == 0:
            ds = dataclasses.replace(ds, vocab=self.cfg.vocab)
        return ds

    def make_pipeline(self) -> Callable[[int], Dict[str, jax.Array]]:
        ds = self.data_spec()
        if ds.kind == "asr":
            return make_pipeline(ds, d_model=self.cfg.d_model,
                                 enc_seq=self.cfg.enc_seq)
        return make_pipeline(ds)

    def init_state(self) -> Tuple[Any, Any]:
        """Seeded model init (``RunSpec.seed``) under this context."""
        with self.activate():
            return self.model.init(jax.random.PRNGKey(self.spec.seed),
                                   self.cfg)

    # ---------------------- gradient compression -----------------------

    def grad_compression(self) -> GradCompression:
        """Resolve ``CompressionSpec`` against this mesh (the logic the
        launcher used to inline): wire kinds run the in-reduction
        collective whenever the mesh can carry it, and degenerate to the
        post-reduce int8 path on a single device, token-for-token."""
        comp = self.spec.compression
        kind = comp.kind
        if kind == "none":
            return GradCompression(False, "1d", "full", None, kind)
        if comp.is_wire:
            layout = comp.resolved_wire_layout(self.n_model)
            wire = self.n_data > 1 or (layout == "2d" and self.n_model > 1)
            if wire:
                return GradCompression(True, layout, "compressed", None,
                                       kind)
            # single device: the wire is a no-op — post-reduce int8 EF IS
            # the compressed path here, token-for-token
            return GradCompression(
                False, layout, "full",
                lambda g, s: ef_compress(g, s, kind="int8"), kind)
        return GradCompression(
            False, "1d", "full",
            lambda g, s: ef_compress(g, s, kind=kind), kind)

    # --------------------------- training ------------------------------

    def make_train_step(self, loss_fn: Optional[Callable] = None,
                        comp: Optional[GradCompression] = None) -> Callable:
        """The pure (pjit-able) train step for this spec, tracing under
        this context.  ``loss_fn`` defaults to the LM loss."""
        comp = comp or self.grad_compression()
        loss_fn = loss_fn or (lambda out, b: lm_loss(out, b["tokens"]))
        step = make_train_step(
            self.forward, loss_fn, self.spec.train, grad_tx=comp.grad_tx,
            reduce=comp.reduce, mesh=self.mesh if comp.wire else None,
            wire_kind=self.spec.compression.wire_kind,
            wire_layout=comp.wire_layout if comp.wire else "auto",
            wire_widths=self.plan,
            wire_fused=self.spec.compression.fused)
        return self.wrap(step)

    def _batch_shardings(self, mesh) -> Dict[str, Any]:
        """Batch-dim shardings for the pipeline's batch dict (tokens are
        ``[B, S]``; ASR batches add ``[B, T, d]`` frame embeddings)."""
        b = self.spec.data.batch
        sh = {"tokens": batch_sharding(mesh, b, 2)}
        if self.spec.data.kind == "asr":
            sh["frame_embeds"] = batch_sharding(mesh, b, 3)
        return sh

    def train_shardings(self, params, qstate, opt,
                        ef_state: Optional[EFState],
                        comp: GradCompression) -> Tuple[tuple, tuple]:
        """(in_shardings, donate_argnums) for the jitted train step."""
        mesh = self.mesh
        in_shardings = (shard_tree(params, mesh, "train"),
                        shard_tree(qstate, mesh, "train"),
                        type(opt)(step=replicated(mesh),
                                  mu=shard_tree(opt.mu, mesh, "train"),
                                  nu=shard_tree(opt.nu, mesh, "train")),
                        self._batch_shardings(mesh),
                        replicated(mesh))
        donate = (0, 2)
        if ef_state is not None:
            layout = self.spec.compression.resolved_residual_layout(
                self.n_model)
            res_sh = (ef_residual_sharding(ef_state.residual, mesh,
                                           layout=layout) if comp.wire
                      else shard_tree(ef_state.residual, mesh, "train"))
            in_shardings += (EFState(residual=res_sh),)
            donate += (5,)  # the residual threads step-to-step like opt
        return in_shardings, donate

    def init_training(self, loss_fn: Optional[Callable] = None
                      ) -> TrainSetup:
        """Params/opt/EF state + the jitted sharded step + pipeline, all
        from the spec alone."""
        params, qstate = self.init_state()
        opt = adamw_init(params)
        comp = self.grad_compression()
        ef_state = comp.init_state(params, self.n_data, self.n_model)
        step_fn = self.make_train_step(loss_fn, comp)
        in_shardings, donate = self.train_shardings(
            params, qstate, opt, ef_state, comp)
        # the state leaves each step on the shardings it came in on (the
        # compiler's own choice may differ, and the next call would then
        # refuse it); metrics are replicated scalars
        out_shardings = (in_shardings[:3] + (replicated(self.mesh),)
                         + in_shardings[5:])
        jitted = jax.jit(step_fn, in_shardings=in_shardings,
                         out_shardings=out_shardings,
                         donate_argnums=donate)
        # place the state where the step leaves it: a step's outputs carry
        # their mesh in their type, so unplaced first inputs would trace
        # and compile the whole step a second time at step 1
        params, qstate, opt = jax.device_put((params, qstate, opt),
                                             in_shardings[:3])
        if ef_state is not None:
            ef_state = jax.device_put(ef_state, in_shardings[5])
        return TrainSetup(self, params, qstate, opt, ef_state, jitted,
                          self.make_pipeline())

    # --------------------------- serving -------------------------------

    def pack_params(self, params: Any) -> Any:
        """The HGQ packed serving tree (``serving/packed.py``), traced
        under this context: int8 per layer by default, nibble-packed int4
        where the spec's :class:`PrecisionPlan` says so (safe on abstract
        trees via eval_shape)."""
        from ..serving.packed import pack_tree
        with self.activate():
            return pack_tree(params, self.plan)

    def make_engine(self, params, qstate, **kwargs):
        """A continuous-batching ``serving.Engine`` serving this spec:
        slot count, packing, KV-cache storage, prefix reuse and admitted
        workloads all come from ``spec.serving`` (plus the spec's
        precision plan), and the engine snapshots this context's trace
        flags, so engines from different contexts coexist in one
        process.  When ``spec.serving.workloads`` includes ``"asr"``
        this builds a :class:`serving.StreamingEngine` — audio-chunk
        requests admitted beside LM traffic, with ``spec.serving.audio``
        setting the arrival chunk and admission cap.  Workload knobs the
        spec does not own (``max_len``, ``eos_id``, ``prefill_chunk``,
        ``seed``) pass through."""
        from ..serving import Engine, StreamingEngine, resolve_kv_bits
        sv = self.spec.serving
        removed = {"batch_slots": "serving.slots",
                   "packed": "serving.packed", "plan": "RunSpec.plan"}
        for kw in removed:
            if kw in kwargs:
                raise TypeError(f"make_engine({kw}=...) was removed: set "
                                f"RunSpec.{removed[kw]} in the spec "
                                f"instead")
        kwargs.setdefault("kv_bits",
                          resolve_kv_bits(sv.kv_cache, self._full_plan))
        kwargs.setdefault("ring_slack", sv.ring_slack or None)
        kwargs.setdefault("prefix_reuse", sv.prefix_reuse)
        cls = Engine
        if "asr" in sv.workloads:
            cls = StreamingEngine
            kwargs.setdefault("audio_chunk", sv.audio.chunk_frames)
            kwargs.setdefault("max_frames", sv.audio.max_frames or None)
        with self.activate(packed=False):
            return cls(self.model, params, qstate, self.cfg,
                       batch_slots=sv.slots,
                       packed=sv.resolved_packed(self.spec.precision),
                       plan=self.plan, **kwargs)

    def plan_summary(self) -> Optional[Dict[str, Any]]:
        """Reporting view of the effective plan (None == uniform int8):
        what dry-run cells and bench JSONs embed."""
        return None if self.plan is None else self.plan.summary()


def build(spec: RunSpec) -> RunContext:
    """``RunSpec -> RunContext``: the one entry point every launcher,
    example, and benchmark shares."""
    return RunContext(spec)
