"""SpmdTrainer: the compiled hybrid-parallel training step.

Reference parity: fleet's hybrid training step (§3.3 of SURVEY — 1F1B loop,
TP allreduces, sharded optimizer, global-norm clip across groups) and the
auto-parallel static pipeline (Engine._prepare_program → Completer →
Partitioner → Resharder, engine.py:1001). TPU-native design: the eager model
code is traced ONCE into a single XLA program per step;

  * TP: parameters carry mp-axis annotations (fleet TP layers) → GSPMD
    partitions matmuls Megatron-style and inserts all-reduce/all-gather on ICI.
  * DP + ZeRO: batch is sharded over (dp, sharding); optimizer state is
    sharded over the sharding axis (ZeRO-1); gradient psum is inserted by the
    compiler (global-view semantics).
  * Remat: decoder blocks wrapped in jax.checkpoint (reference's recompute
    pass, auto_parallel_recompute.py).
  * The optimizer update reuses the SAME `_update` rules as the eager
    optimizers, so eager and compiled training share numerics exactly.

Buffers must be step-invariant (transformers: rope caches). BatchNorm-style
mutable buffers require the jit.to_static path instead.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..autograd.tape import no_grad
from ..utils.jax_compat import shard_map
from ..framework.random import key_context, next_key
from ..profiler import RecordEvent, host_time
from ..optimizer import (ClipGradByGlobalNorm, ClipGradByNorm, ClipGradByValue,
                         Optimizer)
from ..tensor import Tensor
from ..distributed.mesh import KNOWN_AXES, ProcessMesh
from ..distributed.fleet.meta_parallel import get_param_annotation


def make_hybrid_mesh(dp: int = 1, mp: int = 1, pp: int = 1, sharding: int = 1,
                     sep: int = 1, ep: int = 1, dcn=None) -> ProcessMesh:
    """Build the fleet-style hybrid mesh over local devices.

    Axis order (outer→inner): dp, pp, sep, sharding, ep, mp — mp innermost so
    TP collectives ride adjacent-device ICI links (reference topology.py:298
    creates groups in pp->mp->sep->sharding->dp order for the same reason).
    ep shards MoE expert banks (all-to-all dispatch stays within-replica).

    Multi-slice pods: `dcn={"dp": 2}` declares that axis `dp` factors as
    2 (across slices, riding DCN) x dp//2 (within-slice, riding ICI) —
    the jax mesh_utils.create_hybrid_device_mesh recipe, expressed on the
    fleet axis names. Device ids are arranged so the DCN factor of each
    axis is its slowest-varying part: with devices ordered
    slice-major (jax.devices() on TPU pods), every collective on a
    non-DCN axis stays inside one slice, and only the declared axes pay
    DCN latency. The scaling-book layout: dp/pp outermost over DCN,
    tp/sp innermost over ICI.
    """
    degrees = locals()  # the parameters are named after their mesh axes
    names = list(KNOWN_AXES)  # canonical order; never restate it (SHD105)
    shape = [int(degrees[n]) for n in names]
    n = int(np.prod(shape))
    if not dcn:
        mesh = ProcessMesh(shape=shape, dim_names=names,
                           process_ids=list(range(n)))
        mesh.dcn_axes = {}
        return mesh
    dcn_shape = []
    ici_shape = []
    for nm, sz in zip(names, shape):
        f = int(dcn.get(nm, 1))
        if f <= 0 or sz % f:
            raise ValueError(
                f"make_hybrid_mesh: dcn factor {f} does not divide "
                f"{nm}={sz}")
        dcn_shape.append(f)
        ici_shape.append(sz // f)
    unknown = set(dcn) - set(names)
    if unknown:
        raise ValueError(f"make_hybrid_mesh: unknown dcn axes {unknown}")
    k = len(names)
    grid = np.arange(n).reshape(dcn_shape + ici_shape)
    # pair each axis's (dcn-major, ici-minor) factors and merge them
    perm = [ax for i in range(k) for ax in (i, i + k)]
    ids = grid.transpose(perm).reshape(shape)
    mesh = ProcessMesh(shape=shape, dim_names=names,
                       process_ids=ids.reshape(-1).tolist())
    mesh.dcn_axes = dict(dcn)
    return mesh


def _clip_grads_functional(grad_clip, params: Dict, grads: Dict) -> Dict:
    """Functional grad clipping (parity: HybridParallelClipGrad :112 — the
    cross-group norm allreduces are emitted by GSPMD automatically)."""
    if grad_clip is None:
        return grads
    if isinstance(grad_clip, ClipGradByValue):
        return {k: jnp.clip(g, grad_clip.min, grad_clip.max)
                for k, g in grads.items()}
    if isinstance(grad_clip, ClipGradByNorm):
        out = {}
        for k, g in grads.items():
            n = jnp.sqrt(jnp.sum(g.astype(jnp.float32) ** 2))
            scale = jnp.minimum(grad_clip.clip_norm / jnp.maximum(n, 1e-12),
                                1.0)
            out[k] = (g * scale).astype(g.dtype)
        return out
    if isinstance(grad_clip, ClipGradByGlobalNorm):
        total = sum(jnp.sum(g.astype(jnp.float32) ** 2)
                    for g in grads.values())
        gnorm = jnp.sqrt(total)
        scale = grad_clip.clip_norm / jnp.maximum(gnorm, grad_clip.clip_norm)
        return {k: (g * scale).astype(g.dtype) for k, g in grads.items()}
    raise TypeError(f"unsupported grad clip {type(grad_clip)}")


REMAT_POLICIES = {
    # parity target: the reference's recompute strategies (fleet/recompute);
    # TPU-native knob = WHAT jax.checkpoint saves vs recomputes. "dots" is
    # the usual MFU sweet spot for transformer blocks: keep the MXU outputs
    # (matmul activations), recompute the cheap VPU elementwise chains.
    "full": None,                           # save nothing: max memory saving
    "dots": "dots_saveable",                # keep matmul results
    "dots_no_batch": "dots_with_no_batch_dims_saveable",
    "nothing": "nothing_saveable",
}


def _remat_policy(name):
    if name is None or name == "full":
        return None
    import jax.ad_checkpoint as adc
    key = REMAT_POLICIES.get(name)
    if key is None:
        raise ValueError(f"remat_policy must be one of {list(REMAT_POLICIES)},"
                         f" got {name!r}")
    return getattr(adc.checkpoint_policies, key)


def _wrap_remat(layer, policy: str = "full"):
    """Wrap a Layer's forward in jax.checkpoint (activation recompute).

    policy selects what is saved across the backward (REMAT_POLICIES):
    "full" recomputes everything, "dots" keeps MXU matmul outputs, etc."""
    orig = layer.forward
    if getattr(layer, "_remat_wrapped", False):
        return
    pol = _remat_policy(policy)
    ckpt = (jax.checkpoint if pol is None
            else functools.partial(jax.checkpoint, policy=pol))

    def remat_forward(h, *args, **kwargs):
        def pure(h_arr):
            return orig(Tensor(h_arr), *args, **kwargs)._data
        return Tensor(ckpt(pure)(h._data if isinstance(h, Tensor)
                                 else h))
    layer.forward = remat_forward
    layer._remat_wrapped = True


class SpmdTrainer:
    """Compiled training step over a hybrid mesh.

    loss_fn(model, *batch_tensors) -> scalar loss Tensor.
    """

    def __init__(self, model, optimizer: Optimizer, loss_fn: Callable,
                 mesh: Optional[ProcessMesh] = None, remat_layers=None,
                 donate: bool = True, batch_axes=("dp", "sharding"),
                 seq_axis: Optional[str] = None,
                 zero_stage: Optional[int] = None,
                 remat_policy: Optional[str] = None,
                 accumulate_steps: int = 1,
                 aot_cache=None, memwatch=None):
        self.model = model
        self.opt = optimizer
        self.loss_fn = loss_fn
        self.mesh = mesh
        # memory observability plane (profiler/memwatch.py): True/
        # MemWatchConfig/MemoryWatcher arms per-step device-memory
        # snapshots attributed into params/optimizer pools, False
        # disarms, None defers to PADDLE_MEMWATCH / PADDLE_MEMWATCH_DUMP
        # (disarmed = one `is None` check per step)
        from ..profiler.memwatch import resolve_watcher
        self.memwatch = resolve_watcher(memwatch)
        self._mem_pools_tagged = False
        # persistent AOT program cache (paddle_tpu.aot): a path or
        # ArtifactStore enables export/restore of the compiled step,
        # False disables, None defers to the PADDLE_AOT_CACHE env the
        # supervisor threads across restart generations
        self.aot_cache = aot_cache
        # gradient accumulation (reference gradient_merge / non-pipeline
        # accumulate_steps): the batch splits into k micro-batches scanned
        # INSIDE the compiled step — one micro-batch of activations live
        # at a time (k-fold activation-memory saving at equal tokens),
        # f32 grad accumulation, one optimizer update
        self.accumulate_steps = int(accumulate_steps)
        if self.accumulate_steps < 1:
            raise ValueError("accumulate_steps must be >= 1")
        if zero_stage is None:  # group_sharded_parallel() tags take effect
            zero_stage = getattr(optimizer, "_group_sharded_stage",
                                 getattr(model, "_group_sharded_stage", 1))
        if zero_stage not in (0, 1, 2, 3):
            raise ValueError(f"zero_stage must be 0-3, got {zero_stage}")
        self.zero_stage = zero_stage
        self.batch_axes = tuple(a for a in batch_axes
                                if mesh is not None and a in mesh.dim_names
                                and mesh.get_dim_size(a) > 1) or None
        if seq_axis is not None and (mesh is None or
                                     seq_axis not in mesh.dim_names):
            raise ValueError(
                f"seq_axis={seq_axis!r} requires a mesh with that axis "
                f"(mesh={'None' if mesh is None else mesh.dim_names})")
        if seq_axis is not None and mesh.get_dim_size(seq_axis) <= 1:
            seq_axis = None  # degenerate context parallelism = serial
        self.seq_axis = seq_axis
        self.donate = donate
        if remat_policy is None:
            # caller expressed no preference: the perf-config resolver's
            # measured per-device decision (FLAGS_remat_policy, set by
            # flags.apply_perf_config from mfu_lab A/B evidence) wins
            # over the compiled-in "full"; "off" skips wrapping entirely
            # (the measured-faster no-checkpointing side). A flag value
            # outside the known domain (hand-edited config) degrades to
            # "full" — the flag path is advisory, never load-bearing
            from ..framework import flags as _flags
            remat_policy = _flags.flag("remat_policy") or "full"
            if remat_policy not in ("off", "full") and \
                    remat_policy not in REMAT_POLICIES:
                import logging
                logging.getLogger(__name__).warning(
                    "FLAGS_remat_policy=%r is not a known policy; "
                    "using 'full'", remat_policy)
                remat_policy = "full"
        self.remat_policy = remat_policy
        if remat_layers and remat_policy != "off":
            for l in remat_layers:
                _wrap_remat(l, remat_policy)

        self._params: Dict[str, Tensor] = dict(model.named_parameters())
        self._param_list: List[str] = list(self._params)
        self._buffers = {n: b._data for n, b in model.named_buffers()}
        self._jax_mesh = mesh.to_jax() if mesh is not None else None
        self._step_fn = None
        self._opt_state: Optional[Dict] = None
        self._step_count = 0
        self._last_loss = None

    # -- shardings ------------------------------------------------------------
    def _sharding_degree(self) -> int:
        if self.mesh is None or "sharding" not in self.mesh.dim_names:
            return 1
        return self.mesh.get_dim_size("sharding")

    def _zero_entries(self, entries, shape, what: str):
        """Shard the first free, divisible dim over the `sharding` axis.
        Warns on silent fallback to replicated (VERDICT: ZeRO must not
        quietly forfeit its memory win)."""
        deg = self._sharding_degree()
        if deg <= 1 or not shape:
            return entries
        for d in range(len(shape)):
            if entries[d] is None and shape[d] % deg == 0 and shape[d] >= deg:
                entries[d] = "sharding"
                return entries
        import warnings
        warnings.warn(
            f"ZeRO stage {self.zero_stage}: no dim of {what} (shape {shape}) "
            f"is divisible by sharding degree {deg}; it stays replicated",
            stacklevel=3)
        return entries

    def _tp_spec(self, p: Tensor) -> PartitionSpec:
        """TP-annotation-only layout (no ZeRO dims): the gradient's natural
        layout as produced by the backward dots + dp psum."""
        entries = [None] * p._data.ndim
        if self.mesh is not None:
            ann = get_param_annotation(p)
            if ann is not None:
                axis_name, dim = ann
                if axis_name in self.mesh.dim_names and \
                        self.mesh.get_dim_size(axis_name) > 1 and \
                        p._data.shape[dim] % \
                        self.mesh.get_dim_size(axis_name) == 0:
                    entries[dim] = axis_name
        return PartitionSpec(*entries)

    def _param_spec(self, name: str, p: Tensor) -> PartitionSpec:
        if self.mesh is None:
            return PartitionSpec()
        entries = list(self._tp_spec(p))
        if self.zero_stage >= 3:
            # ZeRO-3/FSDP: params live sharded over `sharding`; GSPMD inserts
            # all-gather-on-use in fwd/bwd and reduce-scatter for their grads
            # (reference capability: group_sharded_stage3.py:85,:1077).
            entries = self._zero_entries(entries, p._data.shape,
                                         f"param {name}")
        return PartitionSpec(*entries)

    def _state_spec(self, pspec: PartitionSpec, shape) -> PartitionSpec:
        """ZeRO>=1: additionally shard optimizer state over the sharding axis
        (stage 1/2: params replicated, moments sharded; stage 3: follows the
        already-sharded param spec)."""
        entries = list(pspec) + [None] * (len(shape) - len(list(pspec)))
        if self.zero_stage >= 1 and "sharding" not in entries:
            entries = self._zero_entries(entries, shape, "optimizer state")
        return PartitionSpec(*entries)

    def _grad_spec(self, name: str) -> PartitionSpec:
        """ZeRO>=2: gradients constrained to the sharded layout, so XLA
        lowers the DP gradient sync to reduce-scatter + sharded update +
        all-gather of updated params (reference: group_sharded_stage2.py:47)."""
        p = self._params[name]
        pspec = self._param_spec(name, p)
        return self._state_spec(pspec, p._data.shape)

    def _sharding(self, spec: PartitionSpec):
        return NamedSharding(self._jax_mesh, spec) if self._jax_mesh else None

    def _batch_spec(self, arr) -> PartitionSpec:
        entries = [None] * arr.ndim
        if self.batch_axes:
            entries[0] = self.batch_axes if len(self.batch_axes) > 1 \
                else self.batch_axes[0]
        if self.seq_axis is not None and arr.ndim > 1 and self.mesh and \
                self.seq_axis in self.mesh.dim_names:
            entries[1] = self.seq_axis
        return PartitionSpec(*entries)

    # -- state ----------------------------------------------------------------
    def _init_opt_state(self):
        state = {}
        for name in self._param_list:
            p = self._params[name]
            s = self.opt._init_state(p)
            if self._jax_mesh is not None:
                pspec = self._param_spec(name, p)
                s = {k: jax.device_put(
                        v, self._sharding(self._state_spec(pspec, v.shape)))
                     for k, v in s.items()}
            state[name] = s
        return state

    def _place_params(self):
        """Apply mp/dp shardings to the live model parameters."""
        if self._jax_mesh is None:
            return
        for name in self._param_list:
            p = self._params[name]
            p._data = jax.device_put(
                p._data, self._sharding(self._param_spec(name, p)))

    # -- compiled step --------------------------------------------------------
    def _pure_loss(self, params_, batch_arrays, key):
        """Traceable loss of the full model state dict; subclasses override
        (the pipelined trainer swaps in the stage-stacked block params)."""
        from . import context as pctx
        tensors = [Tensor(a) for a in batch_arrays]
        state = dict(params_)
        state.update(self._buffers)
        with self.model.swap_state(state), key_context(key), no_grad(), \
                pctx.parallel_context(self.mesh, self.batch_axes,
                                      self.seq_axis):
            loss_t = self.loss_fn(self.model, *tensors)
        return loss_t._data.astype(jnp.float32)

    def _lr_mult(self, name: str) -> float:
        p = self._params[name]
        attr = getattr(p, "optimize_attr", None) or {}
        return attr.get("learning_rate", 1.0)

    def _wd(self, name: str) -> float:
        return self.opt._wd_coeff(self._params[name])

    def _update_loop(self, params, grads, opt_state, lr, step_i, asp_masks):
        opt = self.opt
        new_params, new_state = {}, {}
        for n in self._param_list:
            p = params[n]
            g = opt._reg_grad(self._params[n], grads[n].astype(p.dtype),
                              param_arr=p)
            np_, ns_ = opt._update(p, g, opt_state[n],
                                   lr * self._lr_mult(n), self._wd(n), step_i)
            if asp_masks is not None:
                mk = asp_masks.get(id(self._params[n]))
                if mk is not None:
                    np_ = np_ * mk.astype(np_.dtype)
            new_params[n] = np_
            new_state[n] = ns_
        return new_params, new_state

    def _apply_update(self, params, grads, opt_state, lr, step_i):
        """Shared step epilogue: grad clip + per-param optimizer update."""
        opt = self.opt
        with jax.named_scope("clip"):
            grads = _clip_grads_functional(opt._grad_clip, params, grads)
        asp_masks = self._active_asp_masks()
        # the scope the eager Optimizer.step carries too: the update's
        # device time is found by name, whichever path ran it
        with jax.named_scope("optimizer_step"):
            if self._use_sharded_update(asp_masks):
                return self._apply_update_sharded(params, grads, opt_state,
                                                  lr, step_i)
            return self._update_loop(params, grads, opt_state, lr, step_i,
                                     asp_masks)

    @staticmethod
    def _active_asp_masks():
        """ASP: n:m sparsity masks survive compiled updates too (the eager
        path reapplies them in the decorated step(); see incubate/asp.py)."""
        import sys
        asp = sys.modules.get("paddle_tpu.incubate.asp")
        return asp._masks if asp is not None and asp._masks else None

    def _use_sharded_update(self, asp_masks=None) -> bool:
        """ZeRO-3's shard_map update region applies only when the optimizer
        declares a purely elementwise update (opt-in via
        _update_elementwise; Lamb-style global trust ratios would compute
        per-shard norms silently) and no ASP masks are active (masks would
        need slicing into the manual region)."""
        return (self.zero_stage >= 3 and self._jax_mesh is not None
                and asp_masks is None
                and getattr(self.opt, "_update_elementwise", False))

    def _apply_update_sharded(self, params, grads, opt_state, lr, step_i):
        """ZeRO-3: the elementwise optimizer update runs in a shard_map
        manual region over the mesh. The region boundary is a GSPMD
        propagation barrier, so the FSDP 'sharding'-dim layout of the
        params/moments cannot leak backward into the transpose dots (the
        "involuntary full rematerialization" activation reshard); entering
        with the gradient's sharded in_spec lets XLA lower the dp/sharding
        gradient sum to reduce-scatter + local slice — the FSDP contract
        (reference: group_sharded_stage3 grads reduce-scatter,
        group_sharded_stage3.py:85). Requires an elementwise optimizer
        update (Lamb-style trust ratios need global norms and take the
        plain path)."""
        import numpy as _np
        pspecs = {n: self._param_spec(n, self._params[n])
                  for n in self._param_list}
        gspecs = {n: self._grad_spec(n) for n in self._param_list}
        sspecs = {n: {k: self._state_spec(pspecs[n], _np.shape(v))
                      for k, v in opt_state[n].items()}
                  for n in self._param_list}
        rep = PartitionSpec()

        def body(params_, grads_, state_, lr_, step_):
            # lr/step enter as replicated operands (closure capture of
            # tracers is not allowed in a manual region)
            return self._update_loop(params_, grads_, state_, lr_, step_,
                                     None)

        return shard_map(
            body, mesh=self._jax_mesh,
            in_specs=(pspecs, gspecs, sspecs, rep, rep),
            out_specs=(pspecs, sspecs),
            check_vma=False)(params, grads, opt_state, lr, step_i)

    def _check_accumulate_batch(self, batch_arrays):
        k = self.accumulate_steps
        if k > 1:
            for b in batch_arrays:
                if b.ndim < 1 or b.shape[0] % k != 0:
                    raise ValueError(
                        f"accumulate_steps={k} must divide the batch dim "
                        f"of every input (got shape {tuple(b.shape)})")

    def _build(self, batch_arrays):
        k = self.accumulate_steps

        def step_fn(params, opt_state, lr, step_i, key, *batch):
            def grads_of(mb, kk):
                def pure_loss(params_):
                    if self.zero_stage >= 3 and self._jax_mesh is not None:
                        # FSDP compute contract: gather the 'sharding'-
                        # dim-stored params to their TP compute layout
                        # BEFORE the dots (one all-gather per param per
                        # step), instead of letting GSPMD reshard the
                        # activations to match a contraction-dim-sharded
                        # weight (the involuntary-remat tax). The
                        # constraint's VJP pins each gradient to the same
                        # full layout, and the shard_map update boundary
                        # then slices it back to the ZeRO shard — reduce-
                        # scatter + local update, group_sharded_stage3
                        # semantics.
                        params_ = {n: jax.lax.with_sharding_constraint(
                            a, self._sharding(
                                self._tp_spec(self._params[n])))
                            for n, a in params_.items()}
                    return self._pure_loss(params_, mb, kk)

                return jax.value_and_grad(pure_loss)(params)

            if k == 1:
                loss, grads = grads_of(batch, key)
            else:
                micro = tuple(b.reshape((k, b.shape[0] // k)
                                        + b.shape[1:]) for b in batch)
                keys = jax.random.split(key, k)
                g_init = {n: jnp.zeros(params[n].shape, jnp.float32)
                          for n in params}

                def body(carry, xs):
                    mbs, kk = xs
                    l, g = grads_of(tuple(mbs), kk)
                    lc, gc = carry
                    gc = {n: gc[n] + g[n].astype(jnp.float32)
                          for n in gc}
                    return (lc + l.astype(jnp.float32), gc), None

                (loss_s, grad_s), _ = jax.lax.scan(
                    body, (jnp.float32(0.0), g_init), (micro, keys))
                loss = loss_s / k
                grads = {n: (grad_s[n] / k).astype(params[n].dtype)
                         for n in grad_s}
            if 1 <= self.zero_stage <= 2 and self._jax_mesh is not None:
                # Pin each gradient to its NATURAL layout (TP annotation
                # only) first: user annotations are fixed points for GSPMD
                # propagation, so the ZeRO 'sharding'-dim layout of the
                # optimizer state/update cannot leak backward into the
                # transpose dots (where it resharded the ACTIVATIONS from
                # batch- to hidden-sharded — "involuntary full
                # rematerialization", a param-sized all-gather per step;
                # the dryrun asserts this stays fixed). With replicated
                # params (stages 1/2) the TP layout IS the gradient's
                # natural layout, so the pin is free and the subsequent
                # reshard to the ZeRO layout is a local slice of the psum'd
                # gradient. Stage 3 params are stored sharded — there the
                # grads are pinned to the param layout instead (below), the
                # FSDP reduce-scatter contract.
                grads = {n: jax.lax.with_sharding_constraint(
                            g, self._sharding(self._tp_spec(self._params[n])))
                         for n, g in grads.items()}
            use_sharded = self._use_sharded_update(self._active_asp_masks())
            if self._jax_mesh is not None and (
                    self.zero_stage == 2
                    or (self.zero_stage >= 3 and not use_sharded)):
                # Stage 2 (and stage-3 configs the shard_map update cannot
                # serve — Lamb, active ASP masks) pin grads to the ZeRO
                # layout here. Stage 3 with the sharded update skips this:
                # its grads reach the ZeRO layout at the shard_map boundary,
                # and an explicit constraint would only re-open the
                # propagation path into the backward dots.
                grads = {n: jax.lax.with_sharding_constraint(
                            g, self._sharding(self._grad_spec(n)))
                         for n, g in grads.items()}
            new_params, new_state = self._apply_update(params, grads,
                                                       opt_state, lr, step_i)
            return loss, new_params, new_state

        return self._jit_step(step_fn, batch_arrays)

    def _jit_step(self, step_fn, batch_arrays):
        names = self._param_list
        jit_kwargs = {}
        if self._jax_mesh is not None:
            param_sh = {n: self._sharding(self._param_spec(n, self._params[n]))
                        for n in names}
            state_sh = {}
            for n in names:
                pspec = self._param_spec(n, self._params[n])
                state_sh[n] = {
                    k: self._sharding(self._state_spec(pspec, np.shape(v)))
                    for k, v in self._opt_state[n].items()}
            batch_sh = tuple(self._sharding(self._batch_spec(a))
                             for a in batch_arrays)
            rep = self._sharding(PartitionSpec())
            jit_kwargs["in_shardings"] = (param_sh, state_sh, rep, rep, rep,
                                          *batch_sh)
            jit_kwargs["out_shardings"] = (rep, param_sh, state_sh)
        if self.donate:
            jit_kwargs["donate_argnums"] = (0, 1)
        from ..aot.cache import cached_jit, resolve_store
        store = resolve_store(self.aot_cache)
        if store is None:  # cache off: zero extra work on the build path
            return jax.jit(step_fn, **jit_kwargs)
        return cached_jit(
            step_fn, name="spmd_train_step", cache=store,
            key_extras=self._aot_key_extras(), jit_kwargs=jit_kwargs,
            shardings_repr=repr(jit_kwargs.get("in_shardings")))

    def _aot_key_extras(self):
        """Everything the exported step bakes in as constants or closure
        state that the aval/topology/flags/source components of the
        fingerprint cannot see: buffer VALUES (traced as constants),
        optimizer class + scalar hyperparameters, per-param lr/wd
        coefficients, the user's loss/model code (often defined outside
        the package), and the trainer geometry knobs."""
        import hashlib

        from ..aot import fingerprint as _fp

        def scalars(obj):
            if obj is None:
                return None
            items = tuple(sorted(
                (k, v) for k, v in vars(obj).items()
                if isinstance(v, (int, float, str, bool, type(None)))))
            return (type(obj).__module__, type(obj).__name__, items)

        h = hashlib.blake2b(digest_size=16)
        for n in sorted(self._buffers):
            h.update(n.encode())
            h.update(np.ascontiguousarray(
                np.asarray(self._buffers[n])).tobytes())
        for n in self._param_list:
            h.update(repr((n, self._lr_mult(n), self._wd(n))).encode())
        return (
            scalars(self.opt), scalars(self.opt._grad_clip),
            self.zero_stage, self.accumulate_steps, self.batch_axes,
            self.seq_axis, self.donate,
            None if self.mesh is None
            else (tuple(self.mesh.shape), tuple(self.mesh.dim_names)),
            _fp.code_digest(self.loss_fn),
            _fp.code_digest(type(self.model).forward),
            # forward's code alone cannot tell two containers apart
            # (Sequential(..ReLU..) vs Sequential(..GELU..) share param
            # names/shapes AND Sequential.forward); the module digest
            # commits to every sublayer's class/code/scalar attrs
            _fp.module_digest(self.model),
            h.hexdigest(),
        )

    def train_step(self, *batch) -> Tensor:
        """One compiled fwd+bwd+update step. batch: Tensors or arrays."""
        batch_arrays = tuple(b._data if isinstance(b, Tensor) else jnp.asarray(b)
                             for b in batch)
        with RecordEvent("train.step"), host_time.Realm("train"):
            # validated per call: jit retraces on new shapes, and a
            # non-divisible batch must fail with THIS message, not a reshape
            # error deep inside the trace
            self._check_accumulate_batch(batch_arrays)
            if self._opt_state is None:
                self._place_params()
                self._opt_state = self._init_opt_state()
            if self._step_fn is None:
                self._step_fn = self._build(batch_arrays)
            self._step_count += 1
            params = {n: self._params[n]._data for n in self._param_list}
            lr = jnp.float32(self.opt.get_lr())
            loss, new_params, new_state = self._step_fn(
                params, self._opt_state, lr, jnp.float32(self._step_count),
                next_key(), *batch_arrays)
            for n in self._param_list:
                self._params[n]._data = new_params[n]
            self._opt_state = new_state
            self.opt._global_step = self._step_count
            self._last_loss = loss
            if self.memwatch is not None:
                if not self._mem_pools_tagged:
                    self._tag_mem_pools()
                self.memwatch.snapshot(step=self._step_count)
            return Tensor(loss)

    def _tag_mem_pools(self):
        """Register the trainer's array families with the memory watcher
        (profiler/memwatch.py): providers read the LIVE state each
        snapshot, so params updated to fresh arrays every step stay
        attributed without the watcher pinning stale buffers."""
        self.memwatch.register_pool(
            "params", lambda: [self._params[n]._data
                               for n in self._param_list])
        self.memwatch.register_pool(
            "optimizer", lambda: self._opt_state or {})
        self._mem_pools_tagged = True

    def block(self):
        """Wait for every dispatched step: the last loss and the updated
        parameters (the loss of step N is computed from step N-1's
        parameters, so the loss alone would leave the last update in
        flight)."""
        if self._last_loss is not None:
            with RecordEvent("train.block"), host_time.Realm("train"):
                jax.block_until_ready(
                    (self._last_loss,
                     [self._params[n]._data for n in self._param_list]))

    # checkpoint bridge: expose optimizer state in the eager optimizer format
    def sync_optimizer_state(self):
        for n in self._param_list:
            p = self._params[n]
            st = dict(self._opt_state[n])
            st["_step"] = self._step_count
            self.opt._accumulators[id(p)] = st
