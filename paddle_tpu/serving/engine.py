"""ServingEngine: continuous batching over ragged paged attention.

Composes the pieces PR 1-5 left on the table into a serving tier:

  * ``generation.step_ragged`` — ONE jitted XLA program per engine (all
    shapes static: token budget, slot count, page-table width), fed a
    packed mixed-phase batch each step;
  * ``kv_pool.KVBlockPool`` — shared fixed-size pages, ref-counted, with
    hash-chain prefix reuse across requests;
  * ``scheduler.Scheduler`` — admits new requests and evicts finished
    ones at every decode step under a token budget;
  * ``serving.ragged`` — the step's attention: the Pallas paged kernel
    on a single TPU chip, the pure-JAX reference on the CPU and on a mesh.

Greedy sampling runs inside the step program, so a step's tokens feed the
next step on the device: the engine launches step n+1 before it reads step
n's tokens back (one step in flight), and requests stream tokens as they
are read. ``EnginePredictor`` wraps the engine in the
``inference.Predictor`` duck type so ``PredictorPool`` clones and
``BatchingServer`` delegate to ONE shared engine instead of stacking
per-predictor state.
"""
from __future__ import annotations

import threading
import time
from functools import partial
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import generation as _G
from ..profiler import RecordEvent, host_time as _host
from ..profiler import instrument as _instr
from ..resilience import chaos
from . import ragged as _ragged
from . import resilience as _res
from .kv_pool import KVBlockPool
from .locking import OrderedLock
from .obs import resolve_observer
from .scheduler import InFlight, Request, Scheduler, RUNNING, WAITING
from .speculative import make_drafter, verify_greedy


class EngineConfig:
    """Static shapes and options for one engine (one compiled program).

    Speculative decoding: ``spec_method`` = None (off), "ngram"
    (model-free self-drafting), or "draft_model" (requires
    ``draft_model``); ``num_draft_tokens`` is k, the per-sequence draft
    budget a verify step scores; ``spec_options`` are drafter kwargs
    (``max_match``/``min_match`` for ngram, ``context_width``/``quant``
    for draft_model). Speculation changes how many tokens a step can
    emit, never which tokens — greedy output stays bit-identical."""

    def __init__(self, max_seqs: int = 8, token_budget: int = 64,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 max_model_len: Optional[int] = None,
                 enable_prefix_cache: bool = True,
                 quant: Optional[str] = None,
                 spec_method: Optional[str] = None,
                 num_draft_tokens: int = 4, draft_model=None,
                 spec_options: Optional[dict] = None,
                 aot_cache=None, obs=None, memwatch=None,
                 resilience=None, mesh=None, role: Optional[str] = None):
        self.max_seqs = int(max_seqs)
        self.token_budget = int(token_budget)
        self.block_size = int(block_size)
        self.num_blocks = num_blocks
        self.max_model_len = max_model_len
        self.enable_prefix_cache = bool(enable_prefix_cache)
        self.quant = quant
        self.spec_method = spec_method
        self.num_draft_tokens = int(num_draft_tokens)
        self.draft_model = draft_model
        self.spec_options = dict(spec_options) if spec_options else {}
        # persistent AOT program cache (paddle_tpu.aot): a path or
        # ArtifactStore warm-starts ``_engine_step`` from a stored
        # artifact at engine construction, False disables, None defers
        # to the PADDLE_AOT_CACHE env
        self.aot_cache = aot_cache
        # observability plane (serving/obs.py): True/ObsConfig/
        # ServingObserver arms lifecycle tracing + flight recorder + SLO
        # telemetry, False disarms, None defers to PADDLE_SERVE_OBS /
        # PADDLE_SERVE_FLIGHT (disarmed = one `is None` check per seam)
        self.obs = obs
        # memory observability plane (profiler/memwatch.py): per-step
        # device-memory snapshots attributed into params/kv_pages pools
        # with a near-OOM pressure dump; same disarm discipline as obs
        # (None defers to PADDLE_MEMWATCH / PADDLE_MEMWATCH_DUMP)
        self.memwatch = memwatch
        # resilience plane (serving/resilience.py): True/ResilienceConfig
        # arms step-fault containment + drain/replay + admission control,
        # False disarms, None defers to PADDLE_SERVE_RESILIENCE /
        # PADDLE_SERVE_DRAIN_MANIFEST (disarmed = one `is None` check)
        self.resilience = resilience
        # tensor-parallel mesh geometry: None (single chip), an int mp
        # degree, {"mp": n}, a distributed.mesh.ProcessMesh, or a jax
        # Mesh with an "mp" axis — the engine step runs under it with
        # the weights column/row-split at the _qkv_proj/_post_attn
        # seams and the KV pools sharded per-KV-head ([L,P,kvh/mp,bs,hd]
        # per chip), so flagship-sized models serve at all
        self.mesh = mesh
        # disaggregated-serving role (None = unified): "prefill" gives
        # the WHOLE token budget to chunked prefill and never samples —
        # finished prefills export their KV pages to a decode-pool
        # replica (same compiled step program, different budget split);
        # "decode" is the receiving pool's label (still a full engine:
        # the recompute fallback needs it to prefill).
        self.role = role
        if role not in (None, "prefill", "decode"):
            raise ValueError(
                f"unknown engine role {role!r} (want prefill|decode|None)")
        if role == "prefill" and spec_method is not None:
            raise ValueError(
                "a prefill-role engine never decodes — speculative "
                "decoding belongs on the decode pool")
        if spec_method is not None and self.num_draft_tokens < 1:
            raise ValueError(
                f"speculative decoding needs num_draft_tokens >= 1, "
                f"got {self.num_draft_tokens}")


def _resolve_engine_mesh(spec):
    """Normalize ``EngineConfig.mesh`` into a jax Mesh with an ``mp``
    axis (or None for the single-chip engine): an int / {"mp": n} builds
    a 1-D mesh over the first n local devices, a ``ProcessMesh``
    materializes via ``to_jax()``, a jax Mesh passes through. mp degree
    1 resolves to None — a trivial mesh must compile the exact
    single-chip program."""
    if spec is None or spec is False:
        return None
    from jax.sharding import Mesh
    from ..distributed.mesh import ProcessMesh
    if isinstance(spec, ProcessMesh):
        mesh = spec.to_jax()
    elif isinstance(spec, Mesh):
        mesh = spec
    else:
        if isinstance(spec, dict):
            unknown = set(spec) - {"mp"}
            if unknown:
                raise ValueError(
                    f"EngineConfig.mesh dict understands only 'mp' "
                    f"(tensor parallel), got extra axes {sorted(unknown)}")
            mp = int(spec.get("mp", 1))
        else:
            mp = int(spec)
        if mp <= 1:
            return None
        devs = jax.devices()
        if mp > len(devs):
            raise ValueError(
                f"EngineConfig.mesh: mp={mp} needs {mp} devices, this "
                f"process sees {len(devs)}")
        mesh = Mesh(np.asarray(devs[:mp]), ("mp",))
    if "mp" not in mesh.axis_names:
        raise ValueError(
            f"EngineConfig.mesh must define an 'mp' axis (got axes "
            f"{list(mesh.axis_names)})")
    if int(mesh.shape["mp"]) <= 1:
        return None
    return mesh


class _MeshShard:
    """The engine's tensor-parallel annotator: a STATIC jit argument
    (hashable by mesh geometry + device assignment, so jax dispatch and
    the AOT fingerprint both fork per mesh) whose methods pin the packed
    ragged batch to the TP layout at the seams ``generation``'s
    ``_layer_ragged`` exposes — q/k/v per-head right after the
    projection, the attention output (heads-major flatten) right before
    the row-parallel o_proj, and the KV pools per-KV-head."""

    __slots__ = ("mesh", "mp")

    def __init__(self, mesh):
        self.mesh = mesh
        self.mp = int(mesh.shape["mp"])

    def _geometry(self):
        return (tuple(self.mesh.axis_names),
                tuple(self.mesh.devices.shape),
                tuple(d.id for d in self.mesh.devices.flat))

    def __hash__(self):
        return hash(self._geometry())

    def __eq__(self, other):
        return (type(other) is _MeshShard
                and other._geometry() == self._geometry())

    def _c(self, x, *spec):
        from jax.sharding import NamedSharding, PartitionSpec
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, PartitionSpec(*spec)))

    def qkv(self, q, k, v):
        """[T, 1, H|kvh, D] projections: shard the head dim."""
        return (self._c(q, None, None, "mp", None),
                self._c(k, None, None, "mp", None),
                self._c(v, None, None, "mp", None))

    def att(self, att):
        """[T, 1, H*D] attention output: the heads-major flatten keeps
        each shard's heads contiguous, so sharding the last dim IS the
        per-head split feeding the row-parallel o_proj."""
        return self._c(att, None, None, "mp")

    def pools(self, pools):
        """[E, P, kvh, bs, D] stacked pools: per-KV-head shards."""
        return self._c(pools, None, None, "mp", None, None)

    def whole(self, x):
        """Replicated on every chip: the step's output, which the next
        step takes back as ``prev``."""
        return self._c(x)


@jax.jit
def _argmax_rows(logits):
    """Greedy token for EVERY packed row — fixed [T] shape, so the one
    compiled program serves any mix of decode/prefill/verify entries
    (a per-step gather of just the sampling rows would recompile on
    every distinct row-count the speculative planner produces). The step
    program's sampler: the engine hands it in as a static argument."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


@jax.jit
def _all_finite(logits):
    """The StepGuard-style sample guard (serving/resilience.py): one
    fused reduce over the step's logits — NaN/inf anywhere means the
    sampled tokens cannot be trusted and the whole step is a fault.
    Run inside the step program where the nan guard is armed; its
    verdict is the last entry of what the program returns."""
    return jnp.all(jnp.isfinite(logits))


@jax.jit
def _read_page(k_pools, v_pools, src):
    """Gather one physical page's K/V across every cache entry (the
    pools' first axis) — the device half of a KV-page handoff EXPORT.
    ``src`` is a traced scalar, so one compiled program serves every
    page index (a per-export stacked gather would recompile on each
    distinct page count)."""
    return k_pools[:, src], v_pools[:, src]


@partial(jax.jit, donate_argnums=(0, 1))
def _install_page(k_pools, v_pools, k_page, v_page, dst):
    """Scatter one exported page into the receiving pool at ``dst`` —
    the device half of a KV-page handoff IMPORT. Pools donated like the
    engine step; ``dst`` traced, one compile."""
    return (k_pools.at[:, dst].set(k_page),
            v_pools.at[:, dst].set(v_page))


@partial(jax.jit, donate_argnums=(0, 1))
def _copy_page(k_pools, v_pools, src, dst):
    """Copy one physical page across every cache entry of the shared pools —
    the device half of a copy-on-write rollback: the sequence's new
    private boundary page starts as a byte copy of the shared one."""
    return (k_pools.at[:, dst].set(k_pools[:, src]),
            v_pools.at[:, dst].set(v_pools[:, src]))


def _ragged_forward(dec, shard, w, tokens, slot_ids, positions, valid,
                    tables, k_pools, v_pools):
    """Scatter targets from the page tables, ragged attention over the
    pools, logits for every packed token: (logits, beside, k_pools',
    v_pools'), ``beside`` being what the decoder returns beside its logits
    (the pass each row's logits were taken after, where it runs its layers
    several times a token; the routing counts of an expert layer) or None.
    ``step_ragged`` writes this step's rows into the pools in place (each
    page they land in once: by the page kernel where ``ragged.write_path``
    picks it, else in ``generation.page_windows`` windows of the rows,
    sequences and page size; nothing of a pool's size is copied).
    ``shard`` (static, None on a single chip) is the tensor-parallel
    annotator pinning the TP layout through the ragged path."""
    bs = k_pools.shape[3]
    p_total = k_pools.shape[1]
    mp = tables.shape[1]
    windows = _G.page_windows(positions.shape[0], tables.shape[0], bs)
    path = _ragged.write_path(shard, k_pools.shape, k_pools.dtype,
                              positions.shape[0])
    col = positions // bs
    page = jnp.take_along_axis(tables[slot_ids],
                               jnp.clip(col, 0, mp - 1)[:, None], 1)[:, 0]
    # invalid rows write to page index p_total, which mode="drop" discards
    bad = (~valid) | (col >= mp) | (page < 0)
    pages = jnp.where(bad, p_total, page)
    offs = positions % bs
    attend = _ragged.make_attend(tables, slot_ids, positions, valid,
                                 dec.n_heads // dec.n_kv, shard=shard,
                                 scale=dec.attn_scale, latent=dec.latent_dim)
    logits, beside, kp, vp = dec.step_ragged(
        w, tokens, positions, k_pools, v_pools, (pages, offs, windows, path),
        attend, shard=shard)
    if shard is not None:
        # pin the donated outputs to the per-KV-head layout the next
        # step's inputs commit to (no silent gather between steps)
        kp, vp = shard.pools(kp), shard.pools(vp)
    return logits, beside, kp, vp


def _engine_step_impl(dec, shard, sample, check, w, tokens, prev, feed,
                      slot_ids, positions, valid, tables, k_pools, v_pools):
    """The one compiled serving program: ``_ragged_forward`` and the sample
    of every row, so that a step's tokens can feed the next step without
    the host reading them. A row whose token the host has not seen
    (``feed`` >= 0) takes it from ``prev``, the output of the step before,
    still on the device, at that row. Returns ONE int32 array — every row's
    sample (``sample``, static: ``_argmax_rows``), what the decoder returns
    beside its logits behind them, and ``check``'s verdict last where the
    nan guard is armed (static: ``_all_finite``, or None) — and the pools.
    Logits never leave the program. Pools are donated — each step reuses
    the previous buffers. (The un-jitted body, so the AOT cache path can
    close over the static arguments and export a program of array-only
    inputs.)"""
    tokens = jnp.where(feed >= 0, prev[jnp.clip(feed, 0)], tokens)
    logits, beside, kp, vp = _ragged_forward(
        dec, shard, w, tokens, slot_ids, positions, valid, tables, k_pools,
        v_pools)
    out = [sample(logits)]
    if beside is not None:
        out.append(beside.astype(jnp.int32))
    if check is not None:
        out.append(check(logits).astype(jnp.int32)[None])
    out = jnp.concatenate(out) if len(out) > 1 else out[0]
    if shard is not None:
        out = shard.whole(out)
    return out, kp, vp


_engine_step = partial(jax.jit, static_argnums=(0, 1, 2, 3),
                       donate_argnums=(12, 13))(_engine_step_impl)


def _emitted() -> dict:
    """What one engine call read back and handed out, summed over the
    steps it read (``read``)."""
    return {"read": 0, "tokens": 0, "finished": 0, "finished_rids": [],
            "ttfts": [], "accepted": 0, "rollback_pages": 0}


class _Launched:
    """A launched step: its plan, the rows that sample (``(entry, row)``),
    its output array (on the device until read) and its step number."""

    __slots__ = ("plan", "sample_points", "out", "n")

    def __init__(self, plan, sample_points, out, n):
        self.plan, self.sample_points, self.out = plan, sample_points, out
        self.n = n


def _first_tokens(step: _Launched):
    """``serve.emit``'s arguments, from ``step``'s sample points as it is
    read back, and the hand-out's time: the requests whose first token it
    hands out, the sum of their submit -> hand-out on the engine's clock
    (what ``ttfts`` gets) and of the steps each waited on, from the one in
    flight at its submit (or the next) to ``step``; none where it sampled
    nothing."""
    now = time.monotonic()
    if not step.sample_points:
        return {}, now
    n = steps = 0
    waited = 0.0
    for e, _ in step.sample_points:
        req = e.req
        if req.state == RUNNING and req.first_token_at is None:
            n += 1
            waited += now - req.arrival
            mark = req.launch_mark
            steps += 1 if mark is None else step.n - mark + 1
    return {"first_tokens": n, "first_token_s": waited,
            "first_token_steps": steps}, now


class ServingEngine:
    """Continuous-batching LLM serving over one model.

    Thread-safe: ``submit`` may be called from client threads while one
    driver thread calls ``step()`` (steps themselves are serialized)."""

    def __init__(self, model, config: Optional[EngineConfig] = None,
                 seed: int = 0):
        from ..generation import _decoder_for, _quant_weights_cached
        cfg = config or EngineConfig()
        self.model = model
        self.config = cfg
        self.dec = _decoder_for(model)
        mco = getattr(self.dec, "min_capacity_override", None)
        if mco is not None and mco < cfg.token_budget:
            raise ValueError(
                f"MoE _capacity_override={mco} < token_budget "
                f"{cfg.token_budget}: a full step could drop tokens, which "
                "the no-drop decode contract forbids; raise the override "
                "or shrink the budget")
        self.mesh = _resolve_engine_mesh(cfg.mesh)
        self._shard = None
        if self.mesh is not None:
            mp = int(self.mesh.shape["mp"])
            if self.dec.n_kv % mp or self.dec.n_heads % mp:
                raise ValueError(
                    f"EngineConfig.mesh: mp={mp} must divide both "
                    f"num_attention_heads={self.dec.n_heads} and "
                    f"num_key_value_heads={self.dec.n_kv} — the KV pools "
                    "shard per-KV-head and attention per-head")
            self._shard = _MeshShard(self.mesh)
        self._w = (_quant_weights_cached(self.dec, model, cfg.quant)
                   if cfg.quant else self.dec.weights(model))
        self._w = self._shard_weights(self._w)
        max_len = cfg.max_model_len or model.config.max_position_embeddings
        self.max_model_len = int(min(max_len,
                                     model.config.max_position_embeddings))
        bs = cfg.block_size
        self.max_pages_per_seq = -(-self.max_model_len // bs)
        num_blocks = cfg.num_blocks
        if num_blocks is None:
            num_blocks = cfg.max_seqs * self.max_pages_per_seq
        dtype = self._w[self.dec.embed_key].dtype
        # first axis: the K/V cache entries a token keeps, which is the
        # layers of weights only where each runs once a token. A latent
        # cache is ONE pool: its row holds what K and V are both made of
        # (``dec.v_dim`` 0), and the V side is zero wide: no bytes, and the
        # page operations below need no second spelling
        shape = (self.dec.cache_entries, num_blocks, self.dec.n_kv, bs,
                 self.dec.hd)
        self._pool_shape, self._pool_dtype = shape, dtype
        self._kp, self._vp, self._state = (
            self._new_pool(name) for name in ("_kp", "_vp", "_state"))
        # device bytes of one page across K+V and every cache entry — the
        # unit the telemetry/memwatch byte accounting is denominated in
        self.page_bytes = (self._kp.nbytes + self._vp.nbytes) // num_blocks
        self.pool = KVBlockPool(num_blocks, bs, enable_prefix_cache=(
            cfg.enable_prefix_cache and not self._state))
        spec_opts = dict(cfg.spec_options)
        if cfg.spec_method == "draft_model":
            if cfg.draft_model is None:
                raise ValueError(
                    "spec_method='draft_model' needs a draft_model")
            d_cap = cfg.draft_model.config.max_position_embeddings
            if d_cap <= cfg.num_draft_tokens:
                raise ValueError(
                    f"draft model caps at {d_cap} positions, cannot "
                    f"draft {cfg.num_draft_tokens} tokens per step")
            # pin the batched-draft program shape: padding every propose
            # to (max_seqs, width, num_draft_tokens) means ONE compile no
            # matter how the live decode batch and budgets fluctuate
            spec_opts.setdefault("batch_pad", cfg.max_seqs)
            spec_opts.setdefault("draft_k", cfg.num_draft_tokens)
        self.drafter = make_drafter(cfg.spec_method,
                                    draft_model=cfg.draft_model,
                                    **spec_opts)
        self.obs = resolve_observer(cfg.obs)
        from ..profiler.memwatch import resolve_watcher
        self.memwatch = resolve_watcher(cfg.memwatch)
        if self.memwatch is not None:
            for name, pool in self._watched_pools():
                self.memwatch.register_pool(name, pool)
        self.role = cfg.role
        self.sched = Scheduler(self.pool, cfg.max_seqs, cfg.token_budget,
                               self.max_pages_per_seq,
                               drafter=self.drafter,
                               num_draft_tokens=cfg.num_draft_tokens
                               if self.drafter is not None else 0,
                               obs=self.obs, role=cfg.role)
        # disaggregated hand-off plumbing: a router installs a sink
        # (called OUTSIDE the engine lock with (request, export record))
        # to move finished prefills to the decode pool; a standalone
        # prefill engine stashes them for ``pop_handoffs()``
        self.handoff_sink = None
        self._handoff_outbox: List = []
        # two-phase hand-off (the router's transport mode): the exporter
        # KEEPS a request's pages after ``_collect_handoffs`` until the
        # importer's ack decides — ``commit_export`` (landed) or
        # ``abort_export`` (refused / torn / timed out) — so a transfer
        # torn at any byte leaves neither pool holding garbage: either
        # the importer owns good pages, or this pool still does.
        # rid -> retained page list.
        self.handoff_two_phase = False
        self._pending_exports: Dict[int, List[int]] = {}
        # post-step hook (outside the engine lock): the router wires
        # decode replicas to retry deferred hand-offs here, so fleets
        # driven by one thread per replica — not step_all — still drain
        # the pending list as decode queues free up
        self.step_hook = None
        self.kv_handoffs_out = 0
        self.kv_handoffs_in = 0
        self.kv_handoff_pages = 0
        self._tables = np.full((cfg.max_seqs, self.max_pages_per_seq), -1,
                               np.int32)
        self._rng = np.random.default_rng(seed)
        # reentrant; PADDLE_LOCKCHECK=1 arms LOCK_ORDER enforcement
        self._lock = OrderedLock("engine")
        self._work = threading.Event()
        # resilience plane (serving/resilience.py); disarmed = None, and
        # every armed-only seam below is behind one `is None` check
        self.resilience = _res.resolve_resilience(cfg.resilience)
        # the step program's static sampler and, where the nan guard is
        # armed, its check of the logits (looked up here, once)
        self._sample = _argmax_rows
        self._check = _all_finite if (self.resilience is not None and
                                      self.resilience.nan_guard) else None
        self._step_call = self._build_step_call()
        # the last launched step's output (``prev`` of the next launch)
        # and the step launched and not read back yet, if any
        self._prev = self._first_prev()
        self._ahead: Optional[_Launched] = None
        self.aot_warm_result = self._warm_start()
        self.steps = 0
        self.tokens_generated = 0
        self.attn_tiles = self.attn_tiles_ahead = 0
        # what writes the steps' K/V (``_ragged_forward`` asks the same
        # rule); the pages XLA's write moves a visit, its plan's windows
        # (None where the page kernel moves just the pages the rows land
        # in); and the sums over launched steps of the pages moved a visit
        # and of the distinct pages the steps' rows landed in
        self._write_path = _ragged.write_path(
            self._shard, self._pool_shape, self._pool_dtype, cfg.token_budget)
        self._page_windows = (
            _G.page_windows(cfg.token_budget, cfg.max_seqs, bs)
            if self._write_path == "xla" else None)
        self.kv_pages_written = self.kv_page_windows = 0
        # steps launched while the one before was in flight, the decode
        # rows planned, and those of them whose token came from the device
        self.steps_ahead = self.decode_rows = self.device_fed_rows = 0
        # where each launched step's host time went (serve.post's arguments)
        self.host_log = _host.HostLog()
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_rollback_pages = 0
        self._draining = False
        self._admit_cv = threading.Condition()
        self.step_faults = 0
        self.request_retries = 0
        self.requests_failed = 0
        self.shed_total = 0
        self.drains = 0
        # running mean of finished-request e2e seconds: the evidence the
        # retry-after / predicted-queue-wait estimates derive from (two
        # float adds per finished request — always on, cost-free)
        self._e2e_sum = 0.0
        self._e2e_n = 0

    # -- tensor-parallel placement (EngineConfig.mesh) ------------------------
    def _pool_sharding(self):
        """NamedSharding of one stacked pool ([E, P, kvh, bs, D]
        per-KV-head over mp), or None on a single chip."""
        if self.mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec
        return NamedSharding(self.mesh,
                             PartitionSpec(None, None, "mp", None, None))

    def _new_pool(self, name):
        """A zeroed device pool (``"_kp"``, ``"_vp"`` or ``"_state"``) in
        the engine's placement — construction and the step-fault
        containment rebuild share one spelling."""
        if name == "_state":
            return self._new_state()
        # created in place: under a mesh each chip zeroes its own KV-head
        # shard, and no chip ever holds the whole pool
        width = self._pool_shape[-1] if name == "_kp" else self.dec.v_dim
        return jnp.zeros(self._pool_shape[:-1] + (width,), self._pool_dtype,
                         device=self._pool_sharding())

    def _weight_sharding(self, name, ndim):
        """PartitionSpec entries for one weight leaf under the TP mesh:
        the decoder's ``tp_specs`` map, extended to the quantized ::q
        (same layout as the fp matrix) and ::s (the per-output-channel
        scale follows the matrix's OUTPUT split) leaves; anything else —
        or a dim the mp degree does not divide — replicates."""
        specs = self._tp_specs
        if name.endswith("::q"):
            spec = specs.get(name[:-3])
        elif name.endswith("::s"):
            base = specs.get(name[:-3])
            spec = None if base is None else (base[1],)
        else:
            spec = specs.get(name)
        if spec is None:
            return ()
        return spec if len(spec) <= ndim else ()

    def _shard_weights(self, w):
        """Commit every weight leaf to the mesh (column/row TP split per
        ``_weight_sharding``, replicated otherwise) so the one compiled
        step reads per-chip shards; identity on a single chip."""
        if self.mesh is None:
            return w
        from jax.sharding import NamedSharding, PartitionSpec
        mp = int(self.mesh.shape["mp"])
        self._tp_specs = getattr(self, "_tp_specs", None) \
            or self.dec.tp_specs()
        out = {}
        for name, arr in w.items():
            spec = self._weight_sharding(name, jnp.ndim(arr))
            ok = all(s is None or jnp.shape(arr)[d] % mp == 0
                     for d, s in enumerate(spec))
            if not ok:
                spec = ()
            out[name] = jax.device_put(
                arr, NamedSharding(self.mesh, PartitionSpec(*spec)))
        return out

    def _mesh_geometry(self):
        """Hashable/repr-stable mesh descriptor: the AOT fingerprint
        extra that forks cached serve_engine_step artifacts per mesh
        (None vs mp=2 vs mp=4 must never share a program)."""
        if self.mesh is None:
            return None
        return (tuple(self.mesh.axis_names),
                tuple(int(self.mesh.shape[a])
                      for a in self.mesh.axis_names))

    # -- AOT program cache ----------------------------------------------------
    def _build_step_call(self):
        """The engine-step callable: a persistent ``CachedProgram`` when
        an AOT cache is configured (``EngineConfig.aot_cache`` or the
        ``PADDLE_AOT_CACHE`` env), else the plain jitted program."""
        from ..aot.cache import cached_jit, resolve_store
        store = resolve_store(self.config.aot_cache)
        if store is None or self._state:     # a state rides the plain jit
            return self._plain_step_call()
        dec, shard, sample, check = (self.dec, self._shard, self._sample,
                                     self._check)

        def serve_engine_step(w, tokens, prev, feed, slot_ids, positions,
                              valid, tables, k_pools, v_pools):
            return _engine_step_impl(dec, shard, sample, check, w, tokens,
                                     prev, feed, slot_ids, positions, valid,
                                     tables, k_pools, v_pools)

        # _static_key() is what jax.jit's static-argnums dispatch keyed
        # the uncached path on: the decoder's baked-in trace constants
        # (eps, head geometry, n_layers, ...). The class NAME alone
        # would let two same-shape models differing only in eps share
        # one artifact — a wrong hit. stable_repr, not raw repr: the
        # MoE static key holds live function objects whose repr embeds
        # a per-process address (= a permanent spurious miss).
        from ..aot.fingerprint import stable_repr
        jit_kwargs = {"donate_argnums": (8, 9)}
        if self.mesh is not None:
            # warm() lowers from avals ALONE — without explicit
            # in_shardings the exported program would assume unsharded
            # inputs and silently gather the committed TP shards on
            # every real call. Pin the argument layouts the engine
            # actually feeds: per-leaf weight split, replicated host
            # arrays and previous output, per-KV-head pools.
            from jax.sharding import NamedSharding, PartitionSpec
            rep = NamedSharding(self.mesh, PartitionSpec())
            w_sh = {name: arr.sharding for name, arr in self._w.items()}
            pool = self._pool_sharding()
            jit_kwargs["in_shardings"] = (w_sh,) + (rep,) * 7 + (pool, pool)
        return cached_jit(
            serve_engine_step, name="serve_engine_step", cache=store,
            key_extras=(stable_repr(self.dec._static_key()),
                        self.config.quant,
                        getattr(self.dec, "min_capacity_override", None),
                        self.config.block_size, self.max_pages_per_seq,
                        ("mesh", self._mesh_geometry()),
                        ("nan_guard", check is not None)),
            jit_kwargs=jit_kwargs)

    def _first_prev(self):
        """Zeros in the shape, type and placement of the step program's
        output: ``prev`` of the first launch, which feeds no row from it.
        (The decoder says how wide its part is: a trace to read it off
        would cost set-up a second trace of the whole model.)"""
        t_max = self.config.token_budget
        n = t_max + self.dec.beside_width(t_max) + (self._check is not None)
        sharding = None
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            sharding = NamedSharding(self.mesh, PartitionSpec())
        return jnp.zeros((n,), jnp.int32, device=sharding)

    def _warm_start(self) -> Optional[str]:
        """Materialize the one engine program at construction: on a cache
        hit the first real step deserializes instead of re-tracing (the
        serving scale-up story). Returns "hit" | "miss" | "fallback" when
        a cache is configured, None otherwise."""
        if not hasattr(self._step_call, "warm"):
            return None
        t_max = self.config.token_budget
        sds = jax.ShapeDtypeStruct
        i32 = jnp.int32
        w_avals = jax.tree_util.tree_map(
            lambda a: sds(jnp.shape(a), a.dtype), self._w)
        rows = sds((t_max,), i32)
        return self._step_call.warm(
            w_avals, rows, sds(self._prev.shape, i32), rows, rows, rows,
            sds((t_max,), jnp.bool_), sds(self._tables.shape, i32),
            sds(self._kp.shape, self._kp.dtype),
            sds(self._vp.shape, self._vp.dtype))

    # -- client side ----------------------------------------------------------
    def submit(self, prompt: Sequence[int], max_new_tokens: int = 32,
               eos_id: Optional[int] = None, on_token=None,
               stream: bool = False,
               ttft_deadline: Optional[float] = None,
               tpot_deadline: Optional[float] = None,
               generated: Optional[Sequence[int]] = None,
               tag=None, _bypass_admission: bool = False) -> Request:
        """Enqueue one request; returns the Request handle (``result()``
        blocks for the token list, ``stream()`` yields tokens live).
        ``ttft_deadline`` / ``tpot_deadline`` (seconds) are optional SLO
        deadlines the observability plane accounts (violations, goodput,
        attainment — see ``telemetry()``); with the resilience plane's
        ``shed`` policy the TTFT deadline also drives admission.
        ``generated`` seeds already-produced output tokens (restart
        replay: they ride along in ``seq`` for prefix recompute, the
        PR 6 preemption mechanics — decoding continues after them, and
        they are NOT re-delivered to ``on_token``/``stream``). ``tag``
        is an opaque caller identity carried through drain manifests.

        With the resilience plane armed and a bounded queue, this may
        raise ``serving.resilience.AdmissionRejected`` (policies
        ``reject``/``shed``, or a ``block`` timeout) with a structured
        retry-after estimate — overload becomes a clean, typed refusal
        instead of an unbounded queue."""
        with RecordEvent("serve.submit"):
            req = Request(prompt, max_new_tokens=max_new_tokens, eos_id=eos_id,
                          on_token=on_token, stream=stream,
                          ttft_deadline=ttft_deadline,
                          tpot_deadline=tpot_deadline, tag=tag)
            total = len(req.prompt) + req.max_new_tokens
            if total > self.max_model_len:
                raise ValueError(
                    f"prompt {len(req.prompt)} + max_new_tokens "
                    f"{req.max_new_tokens} exceeds max_model_len "
                    f"{self.max_model_len}")
            # the last fed position is total-2 (the final sampled token is
            # never fed), so the worst case is (total-2)//bs + 1 pages
            if (total - 2) // self.pool.block_size + 1 > self.pool.num_blocks:
                raise ValueError(
                    f"request needs more pages than the whole pool "
                    f"({self.pool.num_blocks} x {self.pool.block_size})")
            if generated:
                if len(generated) >= req.max_new_tokens:
                    raise ValueError(
                        f"replay carries {len(generated)} generated tokens "
                        f"but max_new_tokens is {req.max_new_tokens} — "
                        "nothing left to decode")
                req.seq.extend(int(t) for t in generated)
                req.output = [int(t) for t in generated]
            self._admit(req, bypass=_bypass_admission)
            self._work.set()
            _instr.record_serve_queue_depth(self.sched.queue_depth())
            return req

    def _admit(self, req: Request, bypass: bool = False) -> None:
        """Put one request on the waiting queue, applying the resilience
        plane's admission control when armed. Blocking (policy
        ``block``) happens OUTSIDE the engine lock, so the driver thread
        can keep stepping the queue down while submitters wait."""
        res = self.resilience
        if bypass:
            # restart replay (resilience.replay_manifest): the manifest
            # entries were ALREADY admitted once by the dead generation —
            # re-judging the hand-over against the bounded queue could
            # deadlock a block policy (nobody steps during replay) or
            # silently drop accepted work on reject/shed
            with self._lock:
                self._enqueue(req)
            return
        deadline = None
        if res is not None and res.backpressure == "block" and \
                res.block_timeout_s is not None:
            deadline = time.monotonic() + res.block_timeout_s
        while True:
            with self._lock:
                verdict, reason, retry_after, predicted = \
                    self._admission_verdict(req)
                if verdict == "admit":
                    self._enqueue(req)
                    return
                if verdict == "reject":
                    depth = self.sched.queue_depth()
                    self.shed_total += 1
                    _instr.record_serve_shed(res.backpressure)
                    if self.obs is not None:
                        # shed requests still get a complete lifecycle:
                        # submit + exactly one terminal finish event
                        self.obs.on_submit(req)
                        self.obs.on_fail(req, "shed")
                    err = _res.AdmissionRejected(
                        reason, retry_after_s=retry_after,
                        queue_depth=depth, predicted_wait_s=predicted)
                    req.fail(err)
                    raise err
            # verdict == "wait" (policy block): sleep until the driver
            # frees queue room (or drain wakes us to a clean rejection)
            timeout = 0.05
            if deadline is not None:
                timeout = min(timeout, max(deadline - time.monotonic(), 0))
                if timeout <= 0:
                    with self._lock:
                        self.shed_total += 1
                        _instr.record_serve_shed("block")
                        if self.obs is not None:
                            self.obs.on_submit(req)
                            self.obs.on_fail(req, "shed")
                        err = _res.AdmissionRejected(
                            "block_timeout",
                            retry_after_s=self._retry_after_estimate(),
                            queue_depth=self.sched.queue_depth())
                        req.fail(err)
                        raise err
            with self._admit_cv:
                self._admit_cv.wait(timeout=timeout)

    def _enqueue(self, req: Request) -> None:
        """Under the engine lock: put ``req`` on the waiting queue, marked
        with the launch its first token waits on first (the step in
        flight, else the next one)."""
        req.launch_mark = getattr(self._ahead, "n", self.steps + 1)
        self.sched.submit(req)
        if self.obs is not None:
            self.obs.on_submit(req)

    def _admission_verdict(self, req: Request):
        """(verdict, reason, retry_after_s, predicted_wait_s) for one
        candidate under the engine lock. verdict: admit | reject | wait."""
        res = self.resilience
        if res is None:
            return "admit", None, None, None
        if self._draining:
            return "reject", "draining", None, None
        depth = self.sched.queue_depth()
        if res.max_waiting is not None and depth >= res.max_waiting:
            if res.backpressure == "block":
                return "wait", None, None, None
            return "reject", "queue_full", self._retry_after_estimate(), \
                None
        if res.backpressure == "shed" and req.ttft_deadline is not None:
            predicted = self._predicted_wait(depth)
            if predicted is not None and predicted > req.ttft_deadline:
                # SLO-aware shed: admitting would only burn pool pages
                # on a request whose deadline is already lost — refusing
                # it NOW protects the goodput of everyone behind it
                return "reject", "shed", self._retry_after_estimate(), \
                    predicted
        return "admit", None, None, None

    def _service_estimate(self) -> Optional[float]:
        """Mean end-to-end seconds of finished requests (None until the
        engine has finished at least one — no evidence, no estimates)."""
        if self._e2e_n:
            return self._e2e_sum / self._e2e_n
        return None

    def _predicted_wait(self, depth: int) -> Optional[float]:
        """Estimated queue wait for a request arriving at ``depth``:
        the queue ahead of it drains roughly ``max_seqs`` requests per
        mean service time (the continuous batch serves that many
        concurrently)."""
        est = self._service_estimate()
        if est is None:
            return None
        return (depth / max(self.config.max_seqs, 1)) * est

    def _retry_after_estimate(self) -> Optional[float]:
        """Structured backoff hint for a rejected submitter: about one
        batch-slot's worth of service time until queue room opens."""
        est = self._service_estimate()
        if est is None:
            return None
        return est / max(self.config.max_seqs, 1)

    # -- engine side ----------------------------------------------------------
    def step(self) -> bool:
        """Run one continuous-batching step: schedule, launch the step
        program, then read back and hand out the step launched before it
        (sample, evict) — and on a prefill-role engine, export finished
        prefills' KV pages for hand-off to the decode pool. Returns True
        while work remains, a step in flight included.

        At most one step is in flight: step n+1 is planned, packed and
        launched while the chip still runs step n, and a row whose input
        is step n's sample takes it on the device. Where the host must see
        a step's tokens before it plans the next (``_reads_first``), the
        step is read back in the same call.

        Always under the ``serve.step`` span, with its phases inside
        (``serve.schedule``, ``serve.run`` and its four children,
        ``serve.post``): whatever ``jax.profiler`` trace runs sees where
        the host was while the device stood idle."""
        with RecordEvent("serve.step"), _host.Realm("serve"):
            return self._step()

    def _reads_first(self) -> bool:
        """Must the host read a step's tokens before it plans the next?
        Where drafts are made from them (speculation), where a role hands
        requests off, and where the nan guard clears a step before any of
        its tokens reaches a client."""
        return (self.drafter is not None or self.role is not None
                or self._check is not None)

    def _has_work(self) -> bool:
        return self.sched.has_work() or self._ahead is not None

    def _step(self) -> bool:
        obs = self.obs
        armed = obs is not None and obs.armed
        got, record = _emitted(), None
        acct = _host.Interval()        # where the host's time goes: serve.post
        with self._lock:
            acct.locked()
            q0 = self.pool.stats["prefix_queries"]
            h0 = self.pool.stats["prefix_hits"]
            plan = self._schedule(got, armed)
            if not plan.entries:
                # nothing to launch: the step in flight, if any, is read
                self._settle(got, armed)
                # prefill-complete requests can exist even on an empty
                # plan (everything schedulable was already swept):
                # export them so the hand-off never waits on new work
                outbox = self._collect_handoffs()
                # an EMPTY plan is still evidence when something went
                # wrong building it (exhaustion/chaos with nothing
                # schedulable — the wedged-engine case the flight
                # recorder exists for): land its record so the pending
                # anomaly flushes against the step that explains it.
                # Quiet idle polls stay out of the ring.
                if armed and ((plan.explain is not None
                               and (plan.explain["exhaustion"]
                                    or plan.explain["chaos"]))
                              or obs.has_pending()):
                    record = {
                        "step": self.steps, "empty": True,
                        "t_mono_s": round(acct.t0 / 1e9, 6),
                        "plan": plan.explain, "entries": [],
                        "tokens": got["tokens"],
                        "finished": got["finished_rids"],
                        "queue_depth": self.sched.queue_depth(),
                        "running": len(self.sched.running),
                        "pool": {"used": self.pool.used_blocks(),
                                 "cached": self.pool.cached_blocks(),
                                 "free": self.pool.free_blocks(),
                                 "utilization":
                                     round(self.pool.utilization(), 4)},
                    }
                if not self._has_work():
                    self._work.clear()
            else:
                tiles = self._attn_tiles(plan)
                ahead = max(tiles - 1, 0)
                self.attn_tiles += tiles
                self.attn_tiles_ahead += ahead
                fed = self._device_fed(plan)
                self.decode_rows += plan.decode_tokens
                self.device_fed_rows += fed
                written = self._pages_written(plan)
                windows = (written if self._page_windows is None
                           else self._page_windows)
                self.kv_pages_written += written
                self.kv_page_windows += windows
                try:
                    with RecordEvent(
                            "serve.run",
                            prefill_tokens=plan.prefill_tokens,
                            decode_tokens=plan.decode_tokens,
                            first_scheduled=plan.first_scheduled,
                            first_wait_s=plan.first_wait_s,
                            pages_walked=self._pages_walked(plan),
                            pages_tabled=self.config.token_budget
                            * self.max_pages_per_seq,
                            attn_tiles=tiles, attn_tiles_ahead=ahead,
                            layer_visits=self.dec.cache_entries,
                            device_fed_rows=fed,
                            pages_written=written,
                            page_windows=windows,
                            **self._state_counts(plan)):
                        self._run_plan(plan, got, armed)
                except Exception as exc:  # noqa: BLE001 — containment seam
                    if self.resilience is None:
                        # disarmed: the pre-resilience contract — the
                        # swept-but-unexported prefill_done requests stay
                        # in scheduler state, so a router's salvage
                        # manifest still sees them
                        raise
                    self._contain_step_fault(plan, exc, armed, acct)
                    self._notify_admit()
                    return self._has_work()
                # export AFTER the device call landed: a raising step
                # must leave every request somewhere a salvage/requeue
                # can find it, never half-exported in a dropped outbox
                outbox = self._collect_handoffs()
                self.steps += 1
                if self.memwatch is not None:
                    self.memwatch.snapshot(step=self.steps)
            queue_depth = self.sched.queue_depth()
            running = len(self.sched.running)
            util = self.pool.utilization()
            used_blocks = self.pool.used_blocks()
            dq = self.pool.stats["prefix_queries"] - q0
            dh = self.pool.stats["prefix_hits"] - h0
            if armed and plan.entries:
                record = {
                    "step": self.steps, "t_mono_s": round(acct.t0 / 1e9, 6),
                    "plan": plan.explain,
                    "entries": [{"rid": e.req.rid, "start": e.start,
                                 "n": e.n, "draft": len(e.draft)}
                                for e in plan.entries],
                    # what this call read back: the step before this one,
                    # or this one where the host reads first
                    "tokens": got["tokens"],
                    "finished": got["finished_rids"],
                    "accepted": got["accepted"],
                    "rollback_pages": got["rollback_pages"],
                    "pool": {"used": used_blocks,
                             "cached": self.pool.cached_blocks(),
                             "free": self.pool.free_blocks(),
                             "utilization": round(util, 4)},
                    "prefix": {"queries": dq, "hits": dh},
                    "queue_depth": queue_depth, "running": running,
                }
            has_work = self._has_work()
            host = acct.read() if plan.entries or record else None
            if plan.entries:
                self.host_log.add(self.steps, host)
            if armed and record is not None:
                obs.record_step(record, host)
        # -- outside the engine lock: hand-off dispatch, telemetry I/O,
        #    metrics (the sink takes the router lock, and lock order is
        #    always engine -> nothing while dispatching)
        with RecordEvent("serve.post", **(host if plan.entries else {})):
            self._dispatch_handoffs(outbox)
            if self.step_hook is not None:
                self.step_hook()
            if not plan.entries and not got["read"]:
                return has_work
            if plan.entries and armed and obs.telemetry_path and \
                    self.steps % obs.config.telemetry_every == 0:
                # telemetry file I/O happens OUTSIDE the engine lock —
                # telemetry() takes it briefly for the snapshot, but the
                # write must not stall concurrent submit() callers
                obs.write_telemetry(self.telemetry())
            dt = time.monotonic() - acct.t0 / 1e9
            _instr.record_serve_step(
                plan.admitted, got["finished"], plan.preempted,
                queue_depth, running, util, launched=bool(plan.entries))
            _instr.record_serve_kv_pool_bytes(used_blocks * self.page_bytes)
            _instr.record_serve_prefix(dq, dh)
            for lat in got["ttfts"]:
                _instr.record_serve_ttft(lat)
            _instr.record_serve_tokens(got["tokens"], dt)
            if plan.drafted:
                _instr.record_serve_spec_tokens(plan.drafted,
                                                got["accepted"])
            _instr.record_serve_spec_rollback(got["rollback_pages"])
            self._notify_admit()
            return has_work

    def _schedule(self, got: dict, armed: bool):
        """The next plan. One that would preempt a sequence whose next
        token is still on the device waits for that step: it is read back
        first, and the plan made again."""
        try:
            with RecordEvent("serve.schedule"):
                return self.sched.schedule()
        except InFlight:
            self._settle(got, armed)
        with RecordEvent("serve.schedule"):
            return self.sched.schedule()

    def _notify_admit(self) -> None:
        """Wake submitters blocked on queue room (policy ``block``)."""
        if self.resilience is not None:
            with self._admit_cv:
                self._admit_cv.notify_all()

    # -- disaggregated KV-page handoff (prefill -> decode pools) --------------
    def _collect_handoffs(self) -> List:
        """Export every prefill-complete request and detach it from this
        engine (runs under the engine lock): gather the KV page contents
        into standalone device arrays, register the full prompt pages in
        the LOCAL prefix cache (later same-prefix arrivals prefill only
        the tail), release the pages, and queue (request, record) for
        the hand-off sink. After this the request owns nothing here."""
        done = self.sched.pop_prefill_done()
        if not done:
            return []
        self._settle()
        out = []
        now = time.monotonic()
        bs = self.pool.block_size
        for req in done:
            record = self._export_request(req)
            safe = req.pos // bs
            if safe and self.config.enable_prefix_cache:
                # only pages whose FULL content is cached may register —
                # pos can sit mid-page, and a half-written boundary page
                # served as a full-page hit would be garbage K/V
                self.pool.register_prefix(req.seq[:safe * bs],
                                          req.pages[:safe])
            if self.handoff_two_phase:
                # PREPARE: retain the pages — the importer's ack (or its
                # absence) decides commit or abort; releasing now would
                # let the pool recycle pages the transfer may yet need
                self._pending_exports[req.rid] = list(req.pages)
            elif req.pages:
                self.pool.release(req.pages)
            req.pages = []
            # prefill service time: arrival -> hand-off is what this
            # role's wait predictions must price (an e2e figure would
            # never land here — prefill engines finish nothing), so the
            # router's least-loaded fallback and the SLO-aware shed stop
            # mispricing prefill replicas
            self._e2e_sum += now - req.arrival
            self._e2e_n += 1
            self.kv_handoffs_out += 1
            self.kv_handoff_pages += record["num_pages"]
            _instr.record_kv_handoff(record["num_pages"])
            if self.obs is not None:
                self.obs.on_handoff_out(req, record["num_pages"],
                                        record["n_tokens"])
            out.append((req, record))
        return out

    def _export_request(self, req) -> dict:
        """Device half of the KV-page export: one ``_read_page`` gather
        per page (traced index — one compiled program serves every page
        count). The gathered arrays are standalone copies, so releasing
        or even LRU-overwriting the source pages can never touch the
        hand-off. On a multi-host topology THIS is the ICI-transfer
        seam: these arrays would be collective-sent to the decode
        replica's chips; in-process the receiving engine device_puts
        them into its own layout (``_place_page``)."""
        record = self.pool.export_pages(req.pages, req.seq, req.pos)
        ks, vs = [], []
        for p in req.pages:
            k, v = _read_page(self._kp, self._vp, jnp.int32(p))
            ks.append(k)
            vs.append(v)
        record["k"] = ks
        record["v"] = vs
        return record

    def _place_page(self, arr):
        """Commit one incoming page array ([L, kvh, bs, hd]) to this
        engine's device layout — the in-process spelling of the
        cross-replica transfer (a device_put here; an ICI send/recv
        between real hosts). Per-KV-head sharded under a TP mesh,
        matching the pool layout the step program commits to."""
        if self.mesh is None:
            return arr
        from jax.sharding import NamedSharding, PartitionSpec
        return jax.device_put(arr, NamedSharding(
            self.mesh, PartitionSpec(None, "mp", None, None)))

    def import_handoff(self, req, record) -> None:
        """Receive one prefill-complete hand-off INTO this decode-pool
        engine: allocate pages, scatter the exported contents, attach
        pages + position to the request, and queue it — the next step
        feeds the one pending prompt token and samples the first output
        token, bit-identically to a single-engine run. Raises
        ``PoolExhausted`` (or lets a ``serve.kv_alloc`` chaos fault
        through) when pages are unobtainable, with NOTHING mutated — the
        router falls back to ``adopt_recompute``."""
        self._refuse_beside_state("a page hand-off")
        with self._lock:
            if self._draining:
                raise _res.AdmissionRejected(
                    "draining", queue_depth=self.sched.queue_depth())
            if req.done or req in self.sched.waiting \
                    or req in self.sched.running:
                # a duplicated hand-off that evaded transport dedup (the
                # lossy bench's no-dedup baseline runs exactly this):
                # admitting it again would decode the same request twice
                # — refuse with the typed rejection instead
                raise _res.AdmissionRejected(
                    "duplicate_import",
                    queue_depth=self.sched.queue_depth())
            # validate BEFORE allocating: a request this engine's caps
            # can never hold (heterogeneous fleet) must not leak pages
            # or escape the router's fallback ladder as a late raise
            total = len(req.prompt) + req.max_new_tokens
            cap = self.sched.max_pages_per_seq * self.pool.block_size
            if total - 1 > cap:
                raise ValueError(
                    f"hand-off needs up to {total - 1} cached tokens "
                    f"but this engine caps a sequence at {cap}")
            pages = self.pool.import_pages(record)
            try:
                for dst, k, v in zip(pages, record["k"], record["v"]):
                    self._kp, self._vp = _install_page(
                        self._kp, self._vp, self._place_page(k),
                        self._place_page(v), jnp.int32(dst))
            except BaseException:
                # import_pages registered the prefix keys; the scatter
                # never wrote the contents — unregister BEFORE release,
                # or garbage pages would park prefix-matchable
                self.pool.unregister(pages)
                self.pool.release(pages)
                raise
            req.pages = list(pages)
            req.pos = record["n_tokens"]
            req.n_prefix = record["n_tokens"]
            req.state = WAITING
            req.handoff_at = time.monotonic()
            self.sched.submit(req)
            self.kv_handoffs_in += 1
            if self.obs is not None:
                self.obs.on_handoff_in(req, outcome="pages")
        self._work.set()
        _instr.record_serve_queue_depth(self.sched.queue_depth())

    def adopt_recompute(self, req) -> None:
        """The hand-off fallback: take the request WITHOUT its KV pages
        (prefill-replica death mid-handoff, import pool exhausted, chaos
        fault on the import path) and recompute its prompt from scratch
        — the PR 6 preemption mechanics, so greedy output is unchanged.
        Bypasses admission control: the fleet already admitted it once.
        A request THIS engine can never serve (pool or per-sequence cap
        smaller than the request — a misconfigured fleet) resolves with
        a terminal ``RequestFailed`` that also raises to the caller: an
        impossible adoption must never park in the queue forever."""
        with self._lock:
            total = len(req.prompt) + req.max_new_tokens
            bs = self.pool.block_size
            if (total - 2) // bs + 1 > self.pool.num_blocks or \
                    total - 1 > self.sched.max_pages_per_seq * bs:
                err = _res.RequestFailed(req.rid,
                                         reason="recompute_too_large")
                req.fail(err)
                self.requests_failed += 1
                if self.obs is not None:
                    self.obs.on_fail(req, "handoff_failed")
                raise err
            req.pages = []
            req.pos = 0
            req.n_prefix = 0
            req.state = WAITING
            req.handoff_at = time.monotonic()
            self.sched.submit(req)
            self.kv_handoffs_in += 1
            if self.obs is not None:
                self.obs.on_handoff_in(req, outcome="recompute")
        self._work.set()

    def _dispatch_handoffs(self, outbox) -> None:
        """Hand collected exports to the sink (the router's dispatch) —
        OUTSIDE the engine lock, since the sink takes the router lock
        and then a decode replica's lock. Without a sink they stash for
        ``pop_handoffs()`` (standalone prefill engines, tests)."""
        if not outbox:
            return
        sink = self.handoff_sink
        if sink is None:
            self._handoff_outbox.extend(outbox)
            return
        for req, record in outbox:
            sink(req, record)

    def pop_handoffs(self) -> List:
        """Drain the sink-less hand-off stash: (request, record) pairs
        in prefill-completion order. Under the engine lock: the stash
        is appended by ``_dispatch_handoffs`` and a lock-free swap here
        can lose a pair that lands between the read and the reset
        (CCY102 — found by the round-18 concurcheck self-host pass)."""
        with self._lock:
            out, self._handoff_outbox = self._handoff_outbox, []
            return out

    def commit_export(self, rid: int) -> bool:
        """Two-phase hand-off COMMIT: the importer acked ``rid``'s
        prepare — the retained pages release now (and never before: a
        transfer torn at any byte leaves the importer with nothing and
        THIS pool still owning the truth). Idempotent — a torn ack can
        make the router resolve the same prepare twice, and the second
        resolution must find nothing to release."""
        with self._lock:
            pages = self._pending_exports.pop(rid, None)
            if pages is None:
                return False
            self.pool.release(pages)
        return True

    def abort_export(self, rid: int) -> bool:
        """Two-phase hand-off ABORT: the importer refused (or no ack
        ever came) — release the retained pages; the router rebuilds the
        K/V down the recompute ladder. Same idempotent shape as
        ``commit_export``: either verdict leaves this pool clean, the
        two differ only in who owns the K/V afterwards."""
        with self._lock:
            self._settle()
            pages = self._pending_exports.pop(rid, None)
            if pages is None:
                return False
            self.pool.release(pages)
        return True

    # -- step-fault containment (serving/resilience.py) -----------------------
    def _contain_step_fault(self, plan, exc: BaseException, armed: bool,
                            acct) -> None:
        """A raising step never escapes an armed engine. Reset to a
        consistent state: re-zero the device pools if the fault
        invalidated the donated buffers, drop prefix-cache content that
        can no longer be trusted, requeue every running request at the
        waiting front for prefix recompute (generated tokens ride
        along), and FAIL requests past their retry budget with a clean
        terminal error. Runs under the engine lock."""
        res = self.resilience
        # the step in flight, where one is left, is completed first: its
        # tokens reach their clients and ride along in the requeue
        try:
            self._settle(None, armed)
        except Exception:  # noqa: BLE001 — the fault took it down too
            self._drop_ahead()
        if isinstance(exc, _res.StepFault):
            kind = exc.kind
        elif isinstance(exc, chaos.FaultInjected):
            kind = "chaos"
        else:
            kind = type(exc).__name__
        self.step_faults += 1
        _instr.record_serve_step_fault(kind)
        # the donated pools: a fault AFTER the device call consumed the
        # old buffers leaves self._kp/_vp deleted — rebuild them (zeros:
        # every sequence recomputes from scratch anyway)
        pools_rebuilt = False
        for name in ("_kp", "_vp", "_state"):
            if any(a.is_deleted() for a in jax.tree_util.tree_leaves(
                    getattr(self, name))):
                setattr(self, name, self._new_pool(name))
                pools_rebuilt = True
        if pools_rebuilt or kind == "nan_logits":
            # rebuilt pools hold zeros, and garbage logits mean NOTHING
            # device-resident is trustworthy — cached prefix pages
            # included. A pure control-flow fault (chaos error before
            # the device call) keeps the cache: its content was written
            # by successful steps.
            self.pool.drop_cache()
        requeued = self.sched.requeue_all_running(reason=kind)
        self._tables[:] = -1
        failed = []
        for req in requeued:
            if req.step_retries > res.max_step_retries:
                err = _res.RequestFailed(
                    req.rid, reason=f"step_fault:{kind}",
                    retries=req.step_retries - 1, cause=exc)
                self.sched.fail_request(req, err, reason="error")
                failed.append(req)
                self.requests_failed += 1
            else:
                self.request_retries += 1
                _instr.record_serve_request_retry("step_fault")
        if armed:
            self.obs.note_anomaly("step_fault", {
                "kind": kind, "error": repr(exc),
                "requeued": [r.rid for r in requeued if r not in failed],
                "failed": [r.rid for r in failed],
                "retry_budget": res.max_step_retries})
            self.obs.record_step({
                "step": self.steps, "fault": {
                    "kind": kind, "error": repr(exc),
                    "pools_rebuilt": pools_rebuilt,
                    "requeued": [r.rid for r in requeued
                                 if r not in failed],
                    "failed": [r.rid for r in failed]},
                "t_mono_s": round(acct.t0 / 1e9, 6),
                "plan": plan.explain,
                "entries": [{"rid": e.req.rid, "start": e.start,
                             "n": e.n, "draft": len(e.draft)}
                            for e in plan.entries],
                "tokens": 0, "finished": [],
                "queue_depth": self.sched.queue_depth(),
                "running": len(self.sched.running),
                "pool": {"used": self.pool.used_blocks(),
                         "cached": self.pool.cached_blocks(),
                         "free": self.pool.free_blocks(),
                         "utilization": round(self.pool.utilization(), 4)},
            }, acct.read())
        if self.sched.has_work():
            self._work.set()

    def _run_plan(self, plan, got: dict, armed: bool = False) -> None:
        """One planned step, in the four phases its ``serve.*`` spans
        name: pack the host arrays and launch the step program, then read
        back and hand out the step launched before it (``serve.sync``,
        ``serve.emit``), if one is in flight — or this step itself, where
        the host reads first. ``got`` sums what was handed out."""
        # the step-fault drill seam: an injected error here is exactly a
        # device step blowing up with requests mid-flight (contained by
        # _contain_step_fault when the resilience plane is armed)
        chaos.site("serve.engine_step")
        prior = self._ahead
        with RecordEvent("serve.pack"):
            arrays, sample_points = self._pack_plan(plan, armed)
        with RecordEvent("serve.launch"):
            # the page tables go as a COPY: the CPU backend's asarray
            # aliases a numpy array, and the next _pack_plan rewrites
            # these rows while this step may still be running
            tokens, feed, slots, positions, valid = arrays
            out, self._kp, self._vp = self._step_call(
                self._w, jnp.asarray(tokens), self._prev, jnp.asarray(feed),
                jnp.asarray(slots), jnp.asarray(positions),
                jnp.asarray(valid), jnp.array(self._tables), self._kp,
                self._vp)
        self._prev = out
        self._ahead = launched = _Launched(plan, sample_points, out,
                                           self.steps + 1)
        # the fed positions are confirmed at launch, and a sampling row's
        # token is the next plan's to feed from the device
        for e in plan.entries:
            e.req.pos = e.start + e.n      # draft positions confirmed later
        for e, i in sample_points:
            e.req.unread = (launched, i)
        if prior is not None:
            self.steps_ahead += 1
            try:
                self._read(prior, got, armed)
            except BaseException:
                # this step was planned on tokens never handed out
                self._forget(prior)
                self._drop_ahead()
                raise
        if self._reads_first():
            self._settle(got, armed)

    def _settle(self, got: Optional[dict] = None, armed: bool = False
                ) -> None:
        """Read back and hand out the step in flight, if any. Every path
        that reads or moves requests outside a step's own course calls it
        first (under the engine lock)."""
        ahead, self._ahead = self._ahead, None
        if ahead is None:
            return
        try:
            self._read(ahead, _emitted() if got is None else got, armed)
        finally:
            self._forget(ahead)

    def _drop_ahead(self) -> None:
        """Forget the step in flight without reading it (abort, or a fault
        that took its read down)."""
        ahead, self._ahead = self._ahead, None
        if ahead is not None:
            self._forget(ahead)

    @staticmethod
    def _forget(step: _Launched) -> None:
        """Clear the marks of tokens ``step`` sampled that were not read:
        their sequences, requeued or failed, never wait on them."""
        for e, _ in step.sample_points:
            if e.req.unread is not None and e.req.unread[0] is step:
                e.req.unread = None

    def _read(self, step: _Launched, got: dict, armed: bool) -> None:
        """Wait for one launched step (``serve.sync``: the host waits on
        the device) and hand its tokens out (``serve.emit``)."""
        t_max = self.config.token_budget
        counts, all_tok = {}, None
        with RecordEvent("serve.sync"), _host.DeviceWait():
            if step.sample_points or self._check is not None:
                arr = np.asarray(step.out)
                if self._check is not None and not arr[-1]:
                    # garbage logits: fail the STEP before any token of it
                    # can reach a client (pools already swapped —
                    # consistent; the containment path requeues
                    # everything for recompute)
                    raise _res.StepFault(
                        "nan_logits", f"step {self.steps + 1} produced "
                        f"non-finite logits over "
                        f"{step.plan.total_tokens} packed tokens")
                all_tok = arr[:t_max]
                beside = arr[t_max:len(arr) - (self._check is not None)]
                if len(beside) and step.sample_points:
                    counts = self.dec.step_counts(
                        beside, [i for _, i in step.sample_points])
        first, now = _first_tokens(step)
        with RecordEvent("serve.emit", **counts, **first):
            self._emit_sampled(step, all_tok, armed, got, now)
        got["read"] += 1

    # -- a state beside the pages (decoders with ``state_shapes``) -------------
    def _new_state(self) -> list:
        """The pools of what a sequence keeps BESIDE its pages, whatever
        its context: ``[]`` for a decoder without ``state_shapes`` (every
        one whose layers all cache K/V), else ONE entry, a tuple of zeroed
        arrays ``[state layers, max_seqs] + shape``, indexed by the
        request's slot. The step program takes them after the page pools,
        donated like them, and hands them back advanced; nobody clears a
        slot (a sequence's first row at position 0 starts from nothing, in
        the program). What moves pages only cannot keep a state, and is
        refused here in words rather than run wrong: speculation (a
        rejected draft is rolled back by dropping pages; a state cannot be
        un-updated), the prefill/decode roles' page hand-off, a mesh. The
        pool is built with prefix reuse off (a hit would skip rows whose
        state nobody kept), so preemption and the step-fault requeue
        recompute from position 0; an AOT store is passed by."""
        shapes = getattr(self.dec, "state_shapes", None)
        if shapes is None:
            return []
        cfg = self.config
        for what, on in (("spec_method", cfg.spec_method is not None),
                         ("a prefill or decode role", cfg.role is not None),
                         ("a mesh", self.mesh is not None)):
            if on:
                self._refuse_beside_state(what, ValueError)
        layers = self.dec.state_layers
        return [tuple(jnp.zeros((layers, cfg.max_seqs) + tuple(shape),
                                dtype or self._pool_dtype)
                      for shape, dtype in shapes)]

    def _refuse_beside_state(self, what: str, error=RuntimeError) -> None:
        """``what`` moves, shares or shards pages only: not offered for a
        decoder that keeps a state beside them."""
        if getattr(self.dec, "state_shapes", None) is not None:
            raise error(
                f"{what} is not offered for {type(self.model).__name__}: "
                "each sequence keeps a recurrent state beside its K/V pages "
                "(one fixed-size entry a slot), and this path moves, shares "
                "or shards pages only")

    def _plain_step_call(self):
        """The jitted step program with decoder and annotator bound. With a
        state, a call takes and returns what a stateless one does and the
        state pools ride behind the page pools, kept on the engine."""
        statics = (self.dec, self._shard, self._sample, self._check)
        if not self._state:
            return partial(_engine_step, *statics)

        def step_call(*args):
            out, kp, vp, *self._state = _engine_step_state(
                *statics, *args, *self._state)
            return out, kp, vp

        return step_call

    def _watched_pools(self):
        """(name, thunk) of each device pool ``memwatch`` accounts."""
        pools = [("params", lambda: self._w),
                 ("kv_pages", lambda: (self._kp, self._vp))]
        if self._state:
            pools.append(("state", lambda: self._state))
        return pools

    def _state_counts(self, plan) -> dict:
        """``serve.run``'s arguments for a decoder with a state: the
        sequences whose state the step reads and writes, the prefill rows
        (of ``max_seqs`` slots), the prefill rows that pass through it, the
        sequences that begin at position 0. Nothing without one."""
        if not self._state:
            return {}
        return {"state_slots": len(plan.entries),
                "state_slots_max": self.config.max_seqs,
                "state_rows_prefill": plan.prefill_tokens,
                "state_resets": sum(e.start == 0 for e in plan.entries)}

    def _pages_walked(self, plan) -> int:
        """Pages the step's attention has to read: each scheduled
        sequence's live context (its rows and drafts included) in pages.
        ``serve.run`` carries it beside ``pages_tabled``, the whole page
        table once a packed row, which is what the reference gathers."""
        bs = self.config.block_size
        return sum(-(-(e.start + e.n + len(e.draft)) // bs)
                   for e in plan.entries)

    def _pages_written(self, plan) -> int:
        """Distinct pages the step's rows land in, drafts included:
        ``serve.run`` carries it beside ``page_windows``, the pages the
        step program writes a visit (the same pages on the page kernel; on
        XLA's write its plan's windows, whatever the plan). More windows
        than those would lose writes, and ``generation.page_windows``
        bounds them from the packing alone (a sequence's rows at
        consecutive positions), so a plan that breaks it is a fault of the
        engine."""
        bs = self.config.block_size
        written = len({p for e in plan.entries for p in e.req.pages[
            e.start // bs:-(-(e.start + e.n + len(e.draft)) // bs)]})
        if self._page_windows is not None and written > self._page_windows:
            raise RuntimeError(
                f"a step writes {written} pages but its program writes "
                f"{self._page_windows} a visit: generation.page_windows' "
                f"bound does not hold for this plan")
        return written

    def _attn_tiles(self, plan) -> int:
        """Query tiles the step's attention kernel walks: each scheduled
        sequence's rows (drafts included) in tiles of ``ragged_pallas.TQ``.
        All but the first of a step find their first pages already on
        their way, fetched while the tile before was computed: ``serve.run``
        carries the count and that part of it, ``attn_tiles_ahead``."""
        from ..kernels.ragged_pallas import TQ
        tq = min(TQ, self.config.token_budget)
        return sum(-(-(e.n + len(e.draft)) // tq) for e in plan.entries)

    def _device_fed(self, plan) -> int:
        """Rows whose input token is the sample of the step in flight,
        which the program takes on the device (``serve.run`` carries it)."""
        return sum(e.start + e.n > len(e.req.seq) for e in plan.entries)

    def _pack_plan(self, plan, armed: bool):
        """The numpy fill of the step program's inputs (and of the page
        table rows of the scheduled slots), fresh each step. Returns
        ((tokens, feed, slots, positions, valid), sample_points): ``feed``
        is -1, or the row of the step in flight whose sample is the row's
        token; sample_points are (entry, row of its LAST seq token)."""
        t_max = self.config.token_budget
        tokens = np.zeros(t_max, np.int32)
        feed = np.full(t_max, -1, np.int32)
        slots = np.zeros(t_max, np.int32)
        positions = np.zeros(t_max, np.int32)
        valid = np.zeros(t_max, bool)
        sample_points = []
        idx = 0
        for e in plan.entries:
            n, k = e.n, len(e.draft)
            known = e.req.seq[e.start:e.start + n]
            tokens[idx:idx + len(known)] = known
            if len(known) < n:
                feed[idx + n - 1] = e.req.unread[1]
            if k:
                # the verify chunk: drafted tokens ride the SAME packed
                # batch at the positions they would occupy if accepted —
                # to the kernel this is just one more prefill-like chunk
                tokens[idx + n:idx + n + k] = e.draft
            slots[idx:idx + n + k] = e.req.slot
            positions[idx:idx + n + k] = np.arange(e.start, e.start + n + k)
            valid[idx:idx + n + k] = True
            row = self._tables[e.req.slot]
            row[:] = -1
            row[:len(e.req.pages)] = e.req.pages
            if e.samples:
                sample_points.append((e, idx + n - 1))
            if armed and e.start + e.n < len(e.req.seq):
                self.obs.on_prefill(e.req, e.start, e.n)
            idx += n + k
        return (tokens, feed, slots, positions, valid), sample_points

    def _emit_sampled(self, step: _Launched, all_tok, armed: bool,
                      out: dict, now: float) -> None:
        """Hand out one read-back step's sampled tokens (``all_tok``: its
        argmax row per packed token, on the host) at ``now``: verify drafts,
        emit, roll back rejected pages, evict the finished. A row of a
        sequence that finished at an earlier read (an EOS found after this
        step was launched) is dropped. Adds the step's counts to ``out``."""
        plan, sample_points = step.plan, step.sample_points
        if not sample_points:
            return
        finished = []
        accepted = rollback = 0
        for e, i in sample_points:
            req = e.req
            if req.state != RUNNING:
                continue
            if req.unread is not None and req.unread[0] is step:
                req.unread = None
            k = len(e.draft)
            targets = [int(t) for t in all_tok[i:i + k + 1]]
            if k:
                try:
                    chaos.site("serve.spec_verify")
                    _, emitted = verify_greedy(e.draft, targets)
                except chaos.FaultInjected:
                    # full-rejection drill: every draft is discarded,
                    # but the bonus token still lands — the engine
                    # never falls below one token per seq per step
                    emitted = targets[:1]
                    if armed:
                        if plan.explain is not None:
                            plan.explain["chaos"].append(
                                "serve.spec_verify")
                        self.obs.note_anomaly(
                            "chaos_fault",
                            {"site": "serve.spec_verify",
                             "rid": req.rid})
            else:
                emitted = targets[:1]
            used = 0
            for tok in emitted:
                if req.first_token_at is None:
                    req.first_token_at = now
                    out["ttfts"].append(now - req.arrival)
                    if armed:
                        self.obs.on_first_token(req, now - req.arrival)
                req.emit(tok)
                self.tokens_generated += 1
                out["tokens"] += 1
                used += 1
                if (len(req.output) >= req.max_new_tokens
                        or (req.eos_id is not None
                            and tok == req.eos_id)):
                    req.finish_reason = (
                        "eos" if req.eos_id is not None
                        and tok == req.eos_id else "max_new_tokens")
                    finished.append(req)
                    break
            # used-1 drafts were confirmed correct (eos may cut the
            # emission short of the full accepted prefix)
            consumed = used - 1
            accepted += consumed
            if armed:
                self.obs.on_decode(req, used, k, consumed)
            if k:
                req.pos = e.start + e.n + consumed
            if consumed < k:
                # rejected drafts left garbage K/V past the accepted
                # frontier: roll the page list back; copy-on-write if
                # the kept boundary page is shared (rollback must
                # never mutate a page another holder can read)
                kept, released, cow = self.pool.truncate(req.pages,
                                                         req.pos)
                req.pages = kept
                rollback += released
                if cow is not None:
                    self._kp, self._vp = _copy_page(
                        self._kp, self._vp, cow[0], cow[1])
        for req in finished:
            self.sched.evict_finished(req)
            if req.finished_at is not None:
                # service-time evidence the admission-control
                # estimates (retry-after, predicted queue wait)
                # read; a handed-off request clocks from its
                # hand-off, not the original submit — decode-pool
                # estimates must not be polluted by prefill time
                self._e2e_sum += req.finished_at - (
                    req.handoff_at if req.handoff_at is not None
                    else req.arrival)
                self._e2e_n += 1
        out["finished"] += len(finished)
        out["finished_rids"] += [r.rid for r in finished]
        out["accepted"] += accepted
        out["rollback_pages"] += rollback
        self.spec_proposed += plan.drafted
        self.spec_accepted += accepted
        self.spec_rollback_pages += rollback

    def run_until_idle(self, max_steps: Optional[int] = None) -> int:
        """Drive step() until no work remains; returns steps taken."""
        n = 0
        while self.step():
            n += 1
            if max_steps is not None and n >= max_steps:
                break
        return n

    def wait_for_work(self, timeout: Optional[float] = None) -> bool:
        return self._work.wait(timeout)

    def has_work(self) -> bool:
        with self._lock:
            return self._has_work()

    def generate_batch(self, prompts: Sequence[Sequence[int]],
                       max_new_tokens: int = 32,
                       eos_id: Optional[int] = None) -> List[List[int]]:
        """Convenience: submit a batch, drain the engine, return outputs
        in submission order."""
        reqs = [self.submit(p, max_new_tokens=max_new_tokens, eos_id=eos_id)
                for p in prompts]
        self.run_until_idle()
        return [r.result(timeout=0) for r in reqs]

    # -- graceful drain / abort (serving/resilience.py) -----------------------
    def drain(self, deadline_s: Optional[float] = None,
              manifest_path: Optional[str] = None) -> dict:
        """Gracefully wind the engine down: stop admission (late
        ``submit()`` callers get ``AdmissionRejected(reason="draining")``),
        run decode-only until the running set finishes or the grace
        budget expires, then export the restart-replay manifest of every
        UNFINISHED request (prompt + generated tokens + SLO deadlines +
        submission order) — ``resilience.replay_manifest`` feeds it to
        the restarted engine. Returns the manifest dict; writes it
        atomically to ``manifest_path`` (or the resilience config's /
        PADDLE_SERVE_DRAIN_MANIFEST path) when one is named."""
        t0 = time.monotonic()
        with self._lock:
            self._draining = True
            self.sched.draining = True
        self._notify_admit()            # blocked submitters: clean reject
        idle = 0
        while True:
            with self._lock:
                if not self.sched.running:
                    break
            if deadline_s is not None and \
                    time.monotonic() - t0 >= deadline_s:
                break
            before = self.steps
            self.step()
            # a wedged pool (nothing schedulable) must not spin the
            # grace window away: give up after repeated empty plans
            idle = idle + 1 if self.steps == before else 0
            if idle >= 100:
                break
        drain_seconds = time.monotonic() - t0
        with self._lock:
            self._settle()
            manifest = _res.build_manifest(self._live_requests(),
                                           drain_seconds)
            self.drains += 1
        path = manifest_path
        if path is None and self.resilience is not None:
            path = self.resilience.manifest_path
        if path:
            _res.write_manifest(manifest, path)
        _instr.record_serve_drain(drain_seconds)
        if self.obs is not None:
            self.obs.note_anomaly("drain", {
                "drain_seconds": round(drain_seconds, 6),
                "deadline_s": deadline_s,
                "unfinished": len(manifest["requests"]),
                "manifest": path})
        return manifest

    def _live_requests(self) -> List[Request]:
        """Every request this engine is still responsible for (under the
        engine lock), in scheduling order: running, prefill-complete
        awaiting hand-off (swept but not yet dispatched, plus any
        sink-less outbox entries), and waiting. Drain manifests and
        abort_all enumerate THIS — a request mid-handoff must never be
        invisible to a salvage."""
        return (list(self.sched.running) + list(self.sched.prefill_done)
                + [r for r, _ in self._handoff_outbox]
                + list(self.sched.waiting))

    def abort_all(self, exc: Optional[BaseException] = None,
                  reason: str = "engine_abort") -> int:
        """Terminally fail EVERY live request (running + waiting) with a
        clean ``RequestFailed`` and reset pool/slot accounting — the
        last-resort cleanup a front door (``inference.BatchingServer``)
        uses when a disarmed engine's step raised: queued clients get an
        exception instead of a forever-parked Future. Returns how many
        requests were failed. Always available, armed or not."""
        with self._lock:
            # the step in flight is dropped, not read: a request failed
            # here gets no token after its terminal error
            self._drop_ahead()
            live = self._live_requests()
            self._handoff_outbox = []
            # retained two-phase exports: a dead exporter's pending
            # prepares release here; a commit/abort arriving later finds
            # the rid gone (idempotent pop) — never a double release
            for pages in self._pending_exports.values():
                self.pool.release(pages)
            self._pending_exports.clear()
            for req in live:
                err = _res.RequestFailed(req.rid, reason=reason,
                                         retries=req.step_retries,
                                         cause=exc)
                self.sched.fail_request(req, err, reason="error")
            self.requests_failed += len(live)
            self._tables[:] = -1
            if not self._has_work():
                self._work.clear()
        self._notify_admit()
        return len(live)

    def set_role(self, role: Optional[str]) -> None:
        """Re-validate and flip this engine's disaggregated role (the
        autoscaler's rebalance seam). Only legal on an IDLE engine —
        the caller drains first, so every prior request either
        finished or rode the drain manifest onto a survivor. Re-runs
        the construction-time role checks (a prefill engine never
        decodes, so it cannot carry speculative decoding), then
        re-opens admission: the drain that preceded the flip closed
        it."""
        if role not in (None, "prefill", "decode"):
            raise ValueError(
                f"unknown engine role {role!r} (want prefill|decode|None)")
        if role == "prefill" and self.config.spec_method is not None:
            raise ValueError(
                "a prefill-role engine never decodes — speculative "
                "decoding belongs on the decode pool")
        if role is not None:
            self._refuse_beside_state("a prefill or decode role", ValueError)
        with self._lock:
            if self._live_requests():
                raise RuntimeError(
                    "role flip needs an idle engine: drain it first so "
                    "unfinished work hands off to a survivor instead "
                    "of changing roles mid-flight")
            self.role = role
            self.config.role = role
            self.sched.role = role
            # re-admit: the drain that preceded the flip closed the door
            self._draining = False
            self.sched.draining = False
        self._notify_admit()

    def spec_stats(self) -> dict:
        """Lifetime speculative-decoding counters (zeros when off)."""
        p, a = self.spec_proposed, self.spec_accepted
        return {"proposed": p, "accepted": a,
                "accept_rate": a / p if p else 0.0,
                "rollback_pages": self.spec_rollback_pages}

    # -- observability --------------------------------------------------------
    def telemetry(self) -> dict:
        """Engine telemetry snapshot (``tools/serve_top.py`` renders it
        live): step/token counters, queue/pool state and spec stats
        always; SLO attainment, goodput and streaming
        p50/p95/p99 TTFT/TPOT/e2e (bounded quantile sketch) when the
        observability plane is armed."""
        with self._lock:
            s = self.pool.stats
            base = {
                "version": 7,
                "steps": self.steps,
                "tokens_generated": self.tokens_generated,
                "queue_depth": self.sched.queue_depth(),
                "running": len(self.sched.running),
                "pool": {
                    "size": self.pool.num_blocks,
                    "block_size": self.pool.block_size,
                    "used": self.pool.used_blocks(),
                    "cached": self.pool.cached_blocks(),
                    "free": self.pool.free_blocks(),
                    "utilization": round(self.pool.utilization(), 4),
                    "page_bytes": self.page_bytes,
                    "bytes": self.pool.num_blocks * self.page_bytes,
                    "used_bytes": self.pool.used_blocks() * self.page_bytes,
                    "prefix": {"queries": s["prefix_queries"],
                               "hits": s["prefix_hits"],
                               "hit_tokens": s["prefix_hit_tokens"]},
                },
                "spec": self.spec_stats(),
                "attention": _ragged.attention_path(
                    self._shard, self._pool_shape, self._pool_dtype),
                # query tiles its kernel walked, and those of them whose
                # first pages were fetched ahead (all but a step's first)
                "attention_tiles": {"attn_tiles": self.attn_tiles,
                                    "attn_tiles_ahead":
                                        self.attn_tiles_ahead},
                # steps launched while the step before was in flight, and
                # the decode rows whose token the program took from it
                "overlap": {"steps_ahead": self.steps_ahead,
                            "decode_rows": self.decode_rows,
                            "device_fed_rows": self.device_fed_rows},
                # what writes the step's K/V (the page kernel moves each
                # distinct page once, XLA ``page_windows`` windows a visit),
                # the distinct pages the launched steps' rows landed in, and
                # the pages their programs moved a visit (serve.run's, summed)
                "kv_write": {"path": self._write_path,
                             "pages_written": self.kv_pages_written,
                             "page_windows": self.kv_page_windows},
                "host": self.host_log.snapshot(),      # serve.post's, summed
                # two numbers, equal unless the model runs its layers
                # several times a token: the pools are cache_entries deep
                # and what a cached token costs across them, K and V or
                # the one latent row, as the pools hold it
                "model": {"weight_layers": self.dec.n_layers,
                          "cache_entries": self.dec.cache_entries,
                          "cached_token_bytes":
                              self.page_bytes // self.pool.block_size,
                          **self.dec.describe()},
            }
            if self._state:
                # what a sequence keeps beside its pages, whatever its
                # context, and what cannot be kept with it
                leaves = jax.tree_util.tree_leaves(self._state)
                base["model"].update(
                    state_bytes_a_sequence=sum(a.nbytes for a in leaves)
                    // self.config.max_seqs,
                    state_bytes=sum(a.nbytes for a in leaves),
                    prefix_reuse="off: a cached prefix has pages and no "
                                 "state; preempted and requeued requests "
                                 "recompute from position 0")
            if self.mesh is not None:
                base["mesh"] = {"mp": int(self.mesh.shape["mp"]),
                                "devices": self.mesh.devices.size}
            if self.role is not None:
                base["role"] = self.role
                base["handoff"] = {"out": self.kv_handoffs_out,
                                   "in": self.kv_handoffs_in,
                                   "pages": self.kv_handoff_pages}
            if self.drafter is not None:
                base["spec"]["drafter"] = self.drafter.describe()
            if self.memwatch is not None:
                base["mem"] = self.memwatch.telemetry()
            if self.resilience is not None:
                res = self.resilience
                base["resilience"] = {
                    "step_faults": self.step_faults,
                    "request_retries": self.request_retries,
                    "requests_failed": self.requests_failed,
                    "shed_total": self.shed_total,
                    "drains": self.drains,
                    "draining": self._draining,
                    "policy": res.backpressure,
                    "max_waiting": res.max_waiting,
                    "max_step_retries": res.max_step_retries,
                    "service_estimate_s": self._service_estimate(),
                }
            if self.obs is not None:
                return self.obs.telemetry(base)
            return base

    def signals(self) -> dict:
        """One replica's row on the fleet signal bus — the cheap flat
        subset of ``telemetry()`` the ``FleetObserver`` rings every
        ``step_all`` pass (no sketches, no nested spec/mem blocks).
        SLO fields are None when the per-engine obs plane is disarmed:
        the fleet roll-up weights such replicas at zero rather than
        inventing vacuous attainment."""
        with self._lock:
            s = self.pool.stats
            depth = self.sched.queue_depth()
            wait = self._predicted_wait(depth)
            queries = s["prefix_queries"]
            sig = {
                "role": self.role,
                "steps": self.steps,
                "tokens_generated": self.tokens_generated,
                "queue_depth": depth,
                "running": len(self.sched.running),
                "kv_used": self.pool.used_blocks(),
                "kv_size": self.pool.num_blocks,
                "kv_utilization": round(self.pool.utilization(), 4),
                "kv_bytes": self.pool.used_blocks() * self.page_bytes,
                "prefix_queries": queries,
                "prefix_hits": s["prefix_hits"],
                "prefix_hit_rate": round(s["prefix_hits"] / queries, 4)
                if queries else 0.0,
                "handoff_out": self.kv_handoffs_out,
                "handoff_in": self.kv_handoffs_in,
                "handoff_pages": self.kv_handoff_pages,
                "predicted_wait_s": round(wait, 6)
                if wait is not None else None,
            }
            obs = self.obs
        if obs is not None:
            with obs._lock:
                slo = obs.slo
                tracked = slo["tracked"]
                sig.update(
                    finished=obs.counters["finished"],
                    slo_tracked=tracked, slo_met=slo["met"],
                    slo_attainment=round(slo["met"] / tracked, 6)
                    if tracked else None,
                    goodput_tokens=slo["goodput_tokens"],
                    total_tokens=slo["total_tokens"])
        else:
            sig.update(finished=None, slo_tracked=None, slo_met=None,
                       slo_attainment=None, goodput_tokens=None,
                       total_tokens=None)
        return sig

    def dump_flight_record(self, path: Optional[str] = None,
                           reason: str = "manual") -> Optional[dict]:
        """Dump the flight recorder (last N step-plan records + last M
        request lifecycles) to JSON on demand. Returns the record dict,
        or None when the observability plane is disarmed or the dump
        failed — it NEVER raises (``serve.flight_dump`` chaos-drilled)."""
        if self.obs is None:
            return None
        return self.obs.dump(reason=reason, path=path)

    def refresh_weights(self) -> None:
        """Re-snapshot the model weights (after a load_dict / train step).
        The KV pool keeps its content — callers that swapped weights
        should also drop the prefix cache via a fresh engine."""
        from ..generation import _quant_weights_cached
        with self._lock:
            self._w = self._shard_weights(
                _quant_weights_cached(self.dec, self.model,
                                      self.config.quant)
                if self.config.quant
                else self.dec.weights(self.model))


class _BesideState:
    """A decoder as ``_engine_step_impl`` sees it, with the state pools and
    whose each row is bound into its ``step_ragged``; ``state`` holds the
    advanced pools after the call."""

    def __init__(self, dec, state, slot_ids, valid):
        self._dec, self.state = dec, state
        self._rows = (slot_ids, valid)

    def __getattr__(self, name):
        return getattr(self._dec, name)

    def step_ragged(self, *args, **kw):
        *out, self.state = self._dec.step_ragged(
            *args, (self.state, *self._rows), **kw)
        return out


def _engine_step_impl_state(dec, shard, sample, check, w, tokens, prev, feed,
                            slot_ids, positions, valid, tables, k_pools,
                            v_pools, state):
    """``_engine_step_impl`` for a decoder that keeps a state beside the
    pages: the same program with ``state`` (the engine's pools ``[state
    layers, max_seqs, ...]``, donated like the page pools) threaded through
    the decoder's step and returned advanced. A decoder without a state
    never comes here, so its program is what it was."""
    bound = _BesideState(dec, state, slot_ids, valid)
    return (*_engine_step_impl(bound, shard, sample, check, w, tokens, prev,
                               feed, slot_ids, positions, valid, tables,
                               k_pools, v_pools), bound.state)


_engine_step_state = partial(jax.jit, static_argnums=(0, 1, 2, 3),
                             donate_argnums=(12, 13, 14))(
                                 _engine_step_impl_state)


class EnginePredictor:
    """``inference.Predictor``-compatible front door over ONE shared
    engine. ``clone()`` returns another handle to the same engine, so a
    ``PredictorPool`` of these shares the scheduler and KV pool instead
    of holding per-predictor caches; ``BatchingServer`` detects the
    ``engine`` attribute and delegates per-request instead of stacking."""

    def __init__(self, engine: ServingEngine, max_new_tokens: int = 32,
                 eos_id: Optional[int] = None):
        self.engine = engine
        self.max_new_tokens = int(max_new_tokens)
        self.eos_id = eos_id

    def clone(self) -> "EnginePredictor":
        return EnginePredictor(self.engine, self.max_new_tokens,
                               self.eos_id)

    def get_input_names(self) -> List[str]:
        return ["input_ids"]

    def run(self, inputs) -> List[np.ndarray]:
        """inputs: [token_ids] where token_ids is one 1-D prompt or a list
        of 1-D prompts (ragged). Returns [outputs] padded with -1."""
        (ids,) = inputs
        if isinstance(ids, (list, tuple)) and len(ids) and \
                isinstance(ids[0], (list, tuple, np.ndarray)):
            prompts = [list(map(int, p)) for p in ids]     # ragged list
        else:
            arr = np.asarray(ids)
            if arr.ndim == 1:
                prompts = [arr.astype(np.int64).tolist()]
            elif arr.ndim == 2:
                prompts = [row.astype(np.int64).tolist() for row in arr]
            else:
                raise ValueError(
                    f"input_ids must be 1-D, 2-D, or a list of 1-D "
                    f"prompts; got ndim={arr.ndim}")
        outs = self.engine.generate_batch(prompts, self.max_new_tokens,
                                          eos_id=self.eos_id)
        width = max(len(o) for o in outs)
        padded = np.full((len(outs), width), -1, np.int32)
        for i, o in enumerate(outs):
            padded[i, :len(o)] = o
        return [padded]


def engine_from_config(model, config=None, **overrides) -> ServingEngine:
    """Build a ServingEngine honoring ``inference.Config`` serving knobs
    (max_batch_size -> max_seqs, kv-cache block size/capacity -> pool
    geometry, set_speculative_config -> drafter/k); keyword overrides
    win."""
    kw = {}
    for reader in ("serving_options", "speculative_options"):
        opts = getattr(config, reader, None)
        if callable(opts):
            for k, v in opts().items():
                if v is not None:
                    kw[k] = v
    kw.update(overrides)
    if "max_seqs" in kw and "token_budget" not in kw:
        kw["token_budget"] = max(8 * kw["max_seqs"], 64)
    return ServingEngine(model, EngineConfig(**kw))


__all__ = ["EngineConfig", "ServingEngine", "EnginePredictor",
           "engine_from_config"]
