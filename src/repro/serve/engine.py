"""Batched serving engine: device-resident hot loop with continuous
batching.

A fixed pool of ``batch`` slots shares one cache pytree.  The paper's
first discipline is to characterize measurement and dispatch overhead
before trusting any number (§IV.A/§IV.B), and its §VI.D story is that
decode is memory-bound — batching exists to amortize reads.  A serving
loop that pays a host↔device round trip per generated token therefore
measures *dispatch latency*, not the HBM roofline this repo models.  So
the hot path is device-resident:

* **On-device slot state** — ``pos`` / ``remaining`` / ``last_token`` /
  ``active`` / per-request RNG ``seed`` live in device arrays (the
  ``state`` pytree), not host-side Python bookkeeping.
* **Fused multi-token decode** — :meth:`decode_loop` runs K decode
  steps in ONE dispatch: a jitted ``lax.scan`` whose body fuses
  decode → sample → (quantized) cache-write → slot bookkeeping.
  Inactive slots are masked end to end: they neither sample nor write
  (KV ring, slot_pos, and SSM state all hold), so a slot finishing
  mid-loop rides along at zero state cost.  Host code touches tokens
  once per K steps instead of once per token.
* **Chunked pooled prefill for every arch** — admission writes prompt
  chunks directly into the slot's pool region inside a jitted step
  (quantize-on-write for ``kv_format`` caches): ceil(prompt/chunk)
  dispatches of one compiled executable, with no host-side
  rematerialization of the whole cache pytree.  The per-slot
  slot-state protocol (``repro.models.slotstate``) extends this to
  every mixer: SSM/hybrid archs carry conv/ssm state across chunk
  boundaries, enc-dec archs encode once into slot-resident
  enc_out/cross-KV (one ``encode_slot`` dispatch, then the decoder
  prompt chunks), and VLM patch prefixes stream through the same
  chunk executable as precomputed embeddings.  There is no width-1
  prefill or host-side slot scatter anywhere — the fused-loop speedup
  applies to every config in ``repro.configs``.

Sampling inside the loop folds per-slot keys from (request id,
position) — see ``serve.sampler.sample_tokens`` — so token streams are
deterministic per request regardless of batch composition, pool slot,
or whether they came from the fused loop or per-step dispatches.  That
is what makes the fused-vs-per-step equivalence testable for sampled
decoding, not just greedy.

Weight storage: with ``weight_format`` set, the engine keeps its weights
in true quantized storage (``serve.quant.quantize_tree`` — bit-packed
0.5 B/elem fp4 / 0.75 B/elem fp6 via ``repro.lowbits`` when
``packed=True``) as the HBM-resident source of truth, and materializes
the dense compute copy the XLA path consumes.  ``weight_stats`` carries
the *measured* stored-byte counts the Tab VIII benchmark reports.

KV storage: with ``kv_format`` set, the pooled decode cache itself is
blockwise-quantized (``repro.models.attention``: packed fp8/fp4 codes +
1-byte e8m0 scales, quantize-on-write inside the jitted step) — at long
context the KV read, not the weights, dominates decode HBM traffic
(§VI.D).  ``kv_stats`` carries the measured stored KV bytes.  The XLA
decode step materializes a dense dequantized view per layer, so off-TPU
the win is *footprint*; the streaming read win belongs to the Pallas
leg (``repro.kernels.flash_decode_quant``).
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.distributed import sharding as shard_rules
from repro.models.model import Model, build_model
from repro.serve import faults as fault_lib
from repro.serve import spec as spec_lib
from repro.serve.admission import AdmissionConfig, AdmissionQueue, QueueFull
from repro.serve.quant import dequantize_tree, quantize_tree
from repro.serve.sampler import sample_tokens, sample_tokens_chunk
from repro.serve.spec import SpecConfig

# terminal request states; every submitted request ends in exactly one
STATUSES = ("ok",                  # full generation delivered
            "truncated",           # run() step budget hit mid-generation
            "shed",                # dropped by admission policy / cancel
            "deadline_exceeded",   # deadline passed (queued or in-flight)
            "faulted")             # in-loop sentinel caught non-finite
                                   # logits; slot recovered via clear_slot

# emitted-mask codes carried out of the fused scan per (step, slot)
EMIT_NONE = 0      # slot inactive this step
EMIT_TOKEN = 1     # token sampled and appended
EMIT_FAULT = 2     # sentinel tripped: logits went non-finite

# host spans (jax.profiler.TraceAnnotation) at the engine's own phase
# boundaries.  They land in a profile beside the device's operations, on
# one clock, so a trace can put the device's idle time down to the phase
# the host was in; with no profiler running each costs under a
# microsecond.  Admission opens four, a fused decode call two.
SPAN_ADMIT = "engine.admit"            # one request: hand-over to first token
SPAN_CLEAR_SLOT = "engine.clear_slot"  # evict the slot's previous tenant
SPAN_PREFILL = "engine.prefill"        # the prompt's dispatches
SPAN_FIRST_TOKEN = "engine.first_token"  # admission write + its host read
SPAN_DECODE = "engine.decode"          # the fused-loop dispatch
SPAN_HARVEST = "engine.harvest"        # host reads + per-slot bookkeeping
SPANS = (SPAN_ADMIT, SPAN_CLEAR_SLOT, SPAN_PREFILL, SPAN_FIRST_TOKEN,
         SPAN_DECODE, SPAN_HARVEST)
_span = jax.profiler.TraceAnnotation


@dataclasses.dataclass
class GenerationResult:
    request_id: int
    prompt: List[int]
    tokens: List[int]
    status: str = "ok"
    submit_t: Optional[float] = None       # engine-clock timestamps
    first_token_t: Optional[float] = None  # (None when not applicable:
    finish_t: Optional[float] = None       # e.g. shed before prefill)
    admit_t: Optional[float] = None        # handed to a slot

    @property
    def truncated(self) -> bool:
        return self.status == "truncated"

    @property
    def ttft(self) -> Optional[float]:
        if self.submit_t is None or self.first_token_t is None:
            return None
        return self.first_token_t - self.submit_t

    @property
    def queue_wait(self) -> Optional[float]:
        """Time in the queue: submit to the hand-over to a slot."""
        if self.submit_t is None or self.admit_t is None:
            return None
        return self.admit_t - self.submit_t


@dataclasses.dataclass
class _Request:
    request_id: int
    prompt: List[int]
    max_new_tokens: int
    frames: Optional[np.ndarray] = None    # enc-dec source embeddings
    patches: Optional[np.ndarray] = None   # VLM patch-prefix embeddings
    submit_t: float = 0.0                  # engine-clock timestamps
    deadline_s: Optional[float] = None     # absolute (engine clock)
    admit_t: Optional[float] = None
    first_token_t: Optional[float] = None

    @property
    def trunk_len(self) -> int:
        """Decoder-trunk length: VLM patch prefix + text tokens."""
        n_pat = 0 if self.patches is None else self.patches.shape[0]
        return n_pat + len(self.prompt)


class ServeEngine:
    """See module docstring.  ``decode_block`` is K, the number of decode
    steps fused into one dispatch by :meth:`run` (1 = the per-token
    dispatch pattern, kept as the measurable baseline — that leg is what
    ``benchmarks/serve_throughput.py`` compares against)."""

    def __init__(self, model: Model, params, batch: int, max_seq: int,
                 temperature: float = 0.0, top_k: int = 0, seed: int = 0,
                 weight_format: Optional[str] = None, packed: bool = True,
                 kv_format=None, compute_dtype=jnp.bfloat16,
                 decode_block: int = 16, prefill_chunk: int = 32,
                 enc_len: Optional[int] = None,
                 mesh: Optional[Mesh] = None,
                 admission: Optional[AdmissionConfig] = None,
                 clock: Optional[Callable[[], float]] = None,
                 spec: Optional[SpecConfig] = None):
        if kv_format:
            # rebind the model onto a config whose cache layer quantizes:
            # every prefill/decode below then writes packed codes +
            # 1-byte e8m0 scales instead of full-width K/V.  A
            # tuple/list sets PER-POSITION-IN-PERIOD formats
            # (cfg.kv_formats — e.g. fp8 global / fp4 local layers).
            if isinstance(kv_format, (tuple, list)):
                model = build_model(dataclasses.replace(
                    model.cfg, kv_formats=tuple(kv_format)))
            else:
                model = build_model(
                    dataclasses.replace(model.cfg, kv_format=kv_format))
        self.model = model
        self.kv_format = kv_format
        self.weight_store = None
        self.weight_stats: Optional[Dict] = None
        if weight_format is not None:
            self.weight_store, self.weight_stats = quantize_tree(
                params, weight_format, packed=packed)
            params = dequantize_tree(self.weight_store, compute_dtype)
        self.params = params
        self.batch = batch
        self.max_seq = max_seq
        self._temperature = temperature
        self._top_k = top_k
        self.decode_block = max(int(decode_block), 1)
        self._chunked = model.supports_chunked_prefill   # always True now
        self.prefill_chunk = max(
            1, min(int(prefill_chunk), model.min_cache_capacity(max_seq)))
        # enc-dec pools pad every request's source frames to one fixed
        # enc_len so the encode/decode executables compile exactly once
        self.enc_len = ((enc_len or max_seq)
                        if model.cfg.is_encoder_decoder else 0)
        # base sampling key; per-token keys are FOLDED from (request id,
        # position) inside the jitted loop — never split on the host
        self._sample_key = jax.random.PRNGKey(seed)

        # speculative decoding (repro.serve.spec): the fused loop swaps
        # its 1-token decode body for a draft→verify→commit block.
        # Emitted tokens are ALWAYS the true sampled tokens from the
        # verify logits, so greedy AND sampled streams are token-
        # identical to the non-speculative loop by construction.
        self.spec = spec
        self._spec_loops: Dict[int, jax.stages.Wrapped] = {}
        self._spec_tokens = 0     # host totals for spec_report()
        self._spec_blocks = 0
        self._draft_params = None
        self._draft_cache = None
        if spec is not None and spec.draft_model is not None:
            dm: Model = spec.draft_model
            if mesh is not None:
                raise NotImplementedError(
                    "draft-model speculation is single-device; mesh "
                    "serving supports n-gram drafting")
            dcfg = dm.cfg
            if (dcfg.is_encoder_decoder or dcfg.frontend == "vision"
                    or any(blk.mixer != "attn" or blk.cross_attn
                           for blk in dcfg.block_pattern())):
                raise ValueError(
                    f"draft model {dcfg.name} must be a plain decoder-"
                    f"only attention LM (the draft leg reuses the ring "
                    f"slot_pos rollback, which only attention caches "
                    f"support)")
            if model.cfg.is_encoder_decoder or model.cfg.frontend == "vision":
                raise ValueError(
                    f"draft-model speculation needs a plain decoder-only "
                    f"target (got {model.cfg.name}); n-gram drafting "
                    f"covers the other families")
            if dcfg.vocab_size != model.cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {dcfg.vocab_size} != target vocab "
                    f"{model.cfg.vocab_size}")
            self._draft_params = spec.draft_params
            self._draft_cache = dm.init_cache(batch, max_seq)
            self.prefill_chunk = max(1, min(
                self.prefill_chunk, dm.min_cache_capacity(max_seq)))

        self.cache = model.init_cache(batch, max_seq, enc_len=self.enc_len)
        # measured KV storage accounting (codes + scales, what a decode
        # step actually reads) — reported by Tab VIII next to weights
        self.kv_stats: Dict = model.kv_cache_stats(self.cache)

        # host-side request bookkeeping (no per-token state here).  The
        # queue enforces the admission policy (bounded capacity, overload
        # shedding, deadlines, scheduler) entirely on the host — every
        # (policy, scheduler, deadline) combination reuses the exact same
        # compiled executables.
        self.slot_req: List[Optional[_Request]] = [None] * batch
        self.out_tokens: List[List[int]] = [[] for _ in range(batch)]
        self.queue = AdmissionQueue(admission)
        self.results: List[GenerationResult] = []
        self._next_id = 0
        self._submitted = 0
        self._deadlines_live = False
        # injectable clock (deadlines, TTFT): tests/replays substitute a
        # virtual clock via set_clock for deterministic deadline behaviour
        self._clock: Callable[[], float] = clock or time.monotonic
        # watchdog bookkeeping: per-slot (token_count, dispatch_index)
        # snapshots to detect slots that stay active without progressing
        self._dispatches = 0
        self._slot_progress: List[Tuple[int, int]] = [(0, 0)] * batch

        # device-resident slot state
        self.state = self._init_state()

        # mesh-native placement: with a mesh, EVERY array the engine owns
        # gets an explicit NamedSharding from the distributed/sharding
        # rules (params per _param_rule, KV/cross-KV/SSM pools per
        # cache_rule, packed weight store re-fitted onto stored layouts,
        # slot state replicated) before the first executable is built —
        # the jits below then pin their outputs to the same placements,
        # so steady-state serving never triggers a resharding transfer.
        # mesh=None is the exact single-device engine (no placement, no
        # out_shardings, byte-identical dispatch path).
        self.mesh = mesh
        self._sh: Optional[Dict] = None
        if mesh is not None:
            self._sh = shard_rules.serving_shardings(
                model.cfg, mesh, self.params, self.cache, self.state,
                self.weight_store)
            self.params = jax.device_put(self.params, self._sh["params"])
            if self.weight_store is not None:
                self.weight_store = shard_rules.device_put_store(
                    self.weight_store, self._sh["weights"])
            self.cache = jax.device_put(self.cache, self._sh["cache"])
            self.state = jax.device_put(self.state, self._sh["state"])
            self._sample_key = jax.device_put(self._sample_key,
                                              self._sh["replicated"])

        # jitted executables (shared across reset(); decode loops are
        # cached per fused length K).  One executable per admission step
        # kind — token chunks, embed chunks (VLM), encode (enc-dec) —
        # each compiled exactly once (the sanitizer asserts this).
        repl = self._sh["replicated"] if mesh is not None else None
        cache_sh = self._sh["cache"] if mesh is not None else None
        state_sh = self._sh["state"] if mesh is not None else None
        self._loops: Dict[int, jax.stages.Wrapped] = {}
        self._prefill_chunk_fn = self._jit(model.prefill_chunk,
                                           (repl, cache_sh))
        if model.cfg.frontend == "vision":
            self._prefill_embeds_fn = self._jit(
                lambda p, c, emb, slot, off, vl: model.prefill_chunk(
                    p, c, jnp.zeros((emb.shape[1],), jnp.int32), slot,
                    off, vl, embeds=emb),
                (repl, cache_sh))
        if model.cfg.is_encoder_decoder:
            self._encode_slot_fn = self._jit(model.encode_slot, cache_sh)
        self._clear_slot_fn = self._jit(model.clear_slot, cache_sh)
        if self._draft_cache is not None:
            dm = self.spec.draft_model
            self._draft_prefill_fn = self._jit(dm.prefill_chunk)
            self._draft_clear_fn = self._jit(dm.clear_slot)
        self._admit_fn = self._jit(self._admit_update, (repl, state_sh))
        # cancel / fault-arm share _admit_update's shape: one jitted
        # slot-state write each, compiled at most once, dispatched only
        # when a cancel/deadline/fault actually happens
        self._cancel_fn = self._jit(self._cancel_update, state_sh)
        self._fault_arm_fn = self._jit(self._fault_arm_update, state_sh)
        self._fault_cache_fns: Dict[tuple, jax.stages.Wrapped] = {}
        self._cache_sh = cache_sh

    def _jit(self, fn, out_shardings=None):
        """jax.jit, pinning outputs to their serving shardings when the
        engine is mesh-native (mesh=None compiles exactly as before)."""
        if self.mesh is None:
            return jax.jit(fn)
        return jax.jit(fn, out_shardings=out_shardings)

    def _host_read(self, x) -> np.ndarray:
        """The engine's ONE designed device→host sync point per dispatch.

        Mesh-native outputs are replicated (their jits pin P() output
        shardings), so shard 0 already holds the full array — read it
        through the single-device buffer path instead of np.asarray on
        the multi-device Array (which routes through ``._value``, i.e.
        an implicit cross-device fetch the sanitizer rightly counts)."""
        if self.mesh is not None:
            return np.asarray(x.addressable_data(0))
        return np.asarray(x)

    # sampling params are traced INTO the compiled loop/admit
    # executables — mutating them after construction would be silently
    # ignored by the cached jits, so they are read-only (build a new
    # engine to change them)
    @property
    def temperature(self) -> float:
        return self._temperature

    @property
    def top_k(self) -> int:
        return self._top_k

    # -- device state --------------------------------------------------- #
    def _init_state(self) -> Dict[str, jax.Array]:
        b = self.batch
        # fault_pos/fault_kind arm the in-loop logits fault injector:
        # data-driven (a state write, never a recompile), disarmed at -1/0
        state = {"pos": jnp.zeros((b,), jnp.int32),
                 "remaining": jnp.zeros((b,), jnp.int32),
                 "last_token": jnp.zeros((b,), jnp.int32),
                 "active": jnp.zeros((b,), bool),
                 "seed": jnp.zeros((b,), jnp.int32),
                 "fault_pos": jnp.full((b,), -1, jnp.int32),
                 "fault_kind": jnp.zeros((b,), jnp.int32)}
        if self.spec is not None:
            # per-slot speculation state: n-gram history + table (device-
            # resident drafting, zero host traffic) and acceptance
            # accounting (tokens committed / blocks run for the CURRENT
            # tenant; engine totals live on the host).  Non-speculative
            # engines keep the exact 7-field state above.
            state["spec_hist"] = jnp.full(
                (b, self.spec.ngram_context), -1, jnp.int32)
            state["spec_ngram"] = jnp.full(
                (b, self.spec.ngram_table), -1, jnp.int32)
            state["spec_accept"] = jnp.zeros((b,), jnp.int32)
            state["spec_blocks"] = jnp.zeros((b,), jnp.int32)
        return state

    def reset(self) -> None:
        """Clear all serving state (cache, slots, queue, results) while
        keeping compiled executables — benchmark legs reuse one engine so
        recompilation never pollutes a timed region.  The admission
        config survives; use :meth:`set_admission` to swap policies."""
        self.cache = self.model.init_cache(self.batch, self.max_seq,
                                           enc_len=self.enc_len)
        self.state = self._init_state()
        self._spec_tokens = 0
        self._spec_blocks = 0
        if self._draft_cache is not None:
            self._draft_cache = self.spec.draft_model.init_cache(
                self.batch, self.max_seq)
        if self.mesh is not None:
            self.cache = jax.device_put(self.cache, self._sh["cache"])
            self.state = jax.device_put(self.state, self._sh["state"])
        self.slot_req = [None] * self.batch
        self.out_tokens = [[] for _ in range(self.batch)]
        self.queue = AdmissionQueue(self.queue.cfg)
        self.results = []
        self._next_id = 0
        self._submitted = 0
        self._deadlines_live = False
        self._dispatches = 0
        self._slot_progress = [(0, 0)] * self.batch

    # -- clock / policy injection ---------------------------------------- #
    def _now(self) -> float:
        return self._clock()

    def set_clock(self, clock: Callable[[], float]) -> None:
        """Swap the engine clock (deadlines, TTFT stamps).  Virtual
        clocks make deadline tests and trace replays deterministic."""
        self._clock = clock

    def set_admission(self, cfg: Optional[AdmissionConfig]) -> None:
        """Swap the admission policy.  Pending queued requests are
        re-offered under the new policy (overflow is shed per that
        policy) — device state and compiled executables are untouched,
        so scenario sweeps across policies cost zero recompiles."""
        pending = self.queue.drain()
        self.queue = AdmissionQueue(cfg)
        for req in pending:
            try:
                _, shed = self.queue.offer(req)
            except QueueFull:          # block policy: nobody to retry a
                shed = [req]           # config swap, so overflow sheds
            for s in shed:
                self._finish_unadmitted(s, "shed")
        if cfg is not None and cfg.deadline_ms is not None:
            self._deadlines_live = True

    # -- request management -------------------------------------------- #
    def submit(self, prompt: List[int], max_new_tokens: int = 16,
               frames=None, patches=None,
               deadline_ms: Optional[float] = None) -> int:
        """Enqueue a request through the admission policy.

        ``frames`` ((s_src, d_model) float) — REQUIRED for enc-dec archs:
        the source-side frontend embeddings, padded on-device to the
        pool's fixed ``enc_len``.  ``patches`` ((n_patches, d_model)
        float) — optional VLM patch-prefix embeddings, prepended to the
        decoder trunk (early fusion) and streamed through the chunked
        prefill as precomputed embeddings.

        ``deadline_ms`` — relative deadline on the engine clock
        (defaults to the admission config's ``deadline_ms``, if any).
        Expired queued requests finish as ``deadline_exceeded`` without
        ever spending prefill; expired in-flight requests are cancelled
        through the jitted cancel state-write with partial tokens.

        Under a bounded queue the admission policy decides overload:
        ``reject`` finishes the NEW request immediately as ``shed``,
        ``shed_oldest`` sheds the oldest queued request instead, and
        ``block`` raises :class:`QueueFull` (no id is consumed) —
        backpressure belongs to the caller.  Every submitted request is
        accounted: it ends in exactly one :data:`STATUSES` result.

        Prompts must leave room for at least one generated token: a
        trunk of ``max_seq`` or longer used to be admitted anyway,
        setting ``pos`` past the cache so the first decode step attended
        over a silently clipped prefill.  ``max_new_tokens`` must be
        >= 1: admission ALWAYS samples one token from the prefill
        logits, so 0 used to emit a token anyway and write
        ``remaining = -1`` into the slot state."""
        cfg = self.model.cfg
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1 (got {max_new_tokens}): "
                f"admission samples the first token from the prefill "
                f"logits, so a 0-token generation does not exist")
        if cfg.is_encoder_decoder:
            if frames is None:
                raise ValueError(
                    f"{cfg.name} is encoder-decoder: submit() needs "
                    f"frames=(s_src, d_model) source embeddings")
            frames = np.asarray(frames)
            if frames.ndim != 2 or frames.shape[0] < 1:
                raise ValueError(f"frames must be (s_src, d_model); got "
                                 f"{frames.shape}")
            if frames.shape[0] > self.enc_len:
                raise ValueError(
                    f"source length {frames.shape[0]} > pool enc_len "
                    f"{self.enc_len}: raise ServeEngine(enc_len=...)")
        elif frames is not None:
            raise ValueError(f"{cfg.name} is not encoder-decoder: "
                             f"frames= is not accepted")
        if patches is not None:
            if cfg.frontend != "vision":
                raise ValueError(f"{cfg.name} has no vision frontend: "
                                 f"patches= is not accepted")
            patches = np.asarray(patches)
        now = self._now()
        if deadline_ms is None:
            deadline_ms = self.queue.cfg.deadline_ms
        deadline_s = None if deadline_ms is None else now + deadline_ms / 1e3
        req = _Request(self._next_id, list(prompt), max_new_tokens,
                       frames=frames, patches=patches, submit_t=now,
                       deadline_s=deadline_s)
        if req.trunk_len >= self.max_seq:
            raise ValueError(
                f"trunk length {req.trunk_len} (prompt + patch prefix) "
                f">= max_seq {self.max_seq}: the cache holds max_seq-1 "
                f"prompt tokens plus the decode stream; truncate the "
                f"prompt or raise max_seq")
        # offer BEFORE consuming the id: block-policy QueueFull must
        # leave the engine exactly as it was
        accepted, shed = self.queue.offer(req)
        self._next_id += 1
        self._submitted += 1
        if deadline_s is not None:
            self._deadlines_live = True
        for s in shed:
            self._finish_unadmitted(s, "shed")
        return req.request_id

    def _admit_update(self, state, logits, slot, plen, max_new, rid, key,
                      tail=None):
        """Jitted per-admission state write: sample the first token from
        the prefill logits (same (rid, pos) key fold as the loop) and set
        the slot's device state.  One dispatch per admission.

        Speculative engines pass ``tail`` — the last ``prompt_tail``
        prompt tokens, left-padded with -1 — and the slot's n-gram
        history/table is reseeded from it (plus the freshly sampled
        first token) inside the same dispatch."""
        tok = sample_tokens(logits, key, self.temperature, self.top_k,
                            slot_seed=rid[None], pos=plen[None])[0]
        active = max_new > 1
        out = dict(
            state,
            pos=state["pos"].at[slot].set(plen),
            remaining=state["remaining"].at[slot].set(max_new - 1),
            last_token=state["last_token"].at[slot].set(tok),
            active=state["active"].at[slot].set(active),
            seed=state["seed"].at[slot].set(rid),
            fault_pos=state["fault_pos"].at[slot].set(-1),
            fault_kind=state["fault_kind"].at[slot].set(0),
        )
        if self.spec is not None:
            hist, table = spec_lib.seed_from_tail(
                tail, self.spec.ngram_context, self.spec.ngram_table)
            # the first token is already committed — fold it in too
            hist, table = spec_lib.ngram_update(
                hist[None], table[None], tok[None, None],
                jnp.ones((1, 1), bool))
            out["spec_hist"] = state["spec_hist"].at[slot].set(hist[0])
            out["spec_ngram"] = state["spec_ngram"].at[slot].set(table[0])
            out["spec_accept"] = state["spec_accept"].at[slot].set(0)
            out["spec_blocks"] = state["spec_blocks"].at[slot].set(0)
        return tok, out

    def _cancel_update(self, state, slot):
        """Jitted cancel state-write (same shape discipline as
        ``_admit_update``: one dispatch, compiled once): deactivate the
        slot so the next fused block neither samples nor writes for it,
        and disarm any pending fault."""
        return dict(
            state,
            remaining=state["remaining"].at[slot].set(0),
            active=state["active"].at[slot].set(False),
            fault_pos=state["fault_pos"].at[slot].set(-1),
            fault_kind=state["fault_kind"].at[slot].set(0),
        )

    def _fault_arm_update(self, state, slot, pos, kind):
        """Jitted fault-arming state-write: the fused loop corrupts the
        slot's logits when its sampling position reaches ``pos``.  Pure
        data — arming/varying the fault never recompiles the loop."""
        return dict(
            state,
            fault_pos=state["fault_pos"].at[slot].set(pos),
            fault_kind=state["fault_kind"].at[slot].set(kind),
        )

    def _prefill_into_slot(self, slot: int, req: _Request) -> jax.Array:
        """Build the slot's cache region through the slot-state protocol;
        returns last-prompt-position logits (1, vocab).

        Every arch admits the same way: evict the previous tenant's ring
        bookkeeping (``clear_slot``), run the per-request one-shot legs
        (enc-dec: one ``encode_slot`` dispatch writing slot-resident
        enc_out + quantized cross-KV), then stream the decoder trunk —
        VLM patch-embedding chunks first, token chunks after — straight
        into the pool region (jitted; quantize-on-write for kv_format
        caches; SSM conv/state carried across chunk boundaries)."""
        with _span(SPAN_CLEAR_SLOT, slot=slot):
            self.cache = self._clear_slot_fn(self.cache, jnp.int32(slot))
            if self._draft_cache is not None:
                self._draft_cache = self._draft_clear_fn(
                    self._draft_cache, jnp.int32(slot))
        cdtype = jnp.dtype(self.model.cfg.compute_dtype)
        chunk = self.prefill_chunk
        n_pat = 0 if req.patches is None else req.patches.shape[0]
        chunks = -(-n_pat // chunk) + -(-len(req.prompt) // chunk)
        with _span(SPAN_PREFILL, chunks=chunks):
            if req.frames is not None:
                src = req.frames.shape[0]
                padded = np.zeros((1, self.enc_len, req.frames.shape[1]),
                                  np.float32)
                padded[0, :src] = req.frames
                self.cache = self._encode_slot_fn(
                    self.params, self.cache, jnp.asarray(padded, cdtype),
                    jnp.int32(slot), jnp.int32(src))
            offset, logits = 0, None
            if req.patches is not None:
                for off in range(0, n_pat, chunk):
                    part = req.patches[off:off + chunk]
                    valid = part.shape[0]
                    padded = np.zeros((1, chunk, part.shape[1]), np.float32)
                    padded[0, :valid] = part
                    logits, self.cache = self._prefill_embeds_fn(
                        self.params, self.cache, jnp.asarray(padded, cdtype),
                        jnp.int32(slot), jnp.int32(off), jnp.int32(valid))
                offset = n_pat
            for off in range(0, len(req.prompt), chunk):
                part = req.prompt[off:off + chunk]
                valid = len(part)
                part = part + [0] * (chunk - valid)
                logits, self.cache = self._prefill_chunk_fn(
                    self.params, self.cache,
                    jnp.asarray(part, jnp.int32), jnp.int32(slot),
                    jnp.int32(offset + off), jnp.int32(valid))
                if self._draft_cache is not None:
                    # the draft model shares the slot protocol: its cache
                    # is prefilled through the same chunk stream (draft-
                    # model targets are plain decoder-only: offset == 0)
                    _, self._draft_cache = self._draft_prefill_fn(
                        self._draft_params, self._draft_cache,
                        jnp.asarray(part, jnp.int32), jnp.int32(slot),
                        jnp.int32(offset + off), jnp.int32(valid))
            return logits

    def _admit(self) -> None:
        for slot in range(self.batch):
            if self.slot_req[slot] is not None or not self.queue:
                continue
            now = self._now()
            req, expired = self.queue.take(now)
            for e in expired:
                # deadline passed while queued: account it WITHOUT
                # spending a single prefill dispatch on it
                self._finish_unadmitted(e, "deadline_exceeded")
            if req is None:
                continue
            req.admit_t = now
            with _span(SPAN_ADMIT, request_id=req.request_id, slot=slot,
                       prompt_len=req.trunk_len):
                logits = self._prefill_into_slot(slot, req)
                args = [self.state, logits, jnp.int32(slot),
                        jnp.int32(req.trunk_len),
                        jnp.int32(req.max_new_tokens),
                        jnp.int32(req.request_id), self._sample_key]
                if self.spec is not None:
                    ptail = self.spec.prompt_tail
                    tail = np.full((ptail,), -1, np.int32)
                    got = req.prompt[-ptail:]
                    if got:
                        tail[-len(got):] = got
                    args.append(jnp.asarray(tail))
                with _span(SPAN_FIRST_TOKEN):
                    tok, self.state = self._admit_fn(*args)
                    first = int(self._host_read(tok))
                self.slot_req[slot] = req
                self.out_tokens[slot] = [first]
                req.first_token_t = self._now()
            self._slot_progress[slot] = (1, self._dispatches)
            if req.max_new_tokens <= 1:
                self._finish(slot)

    # -- fused decode --------------------------------------------------- #
    def _make_decode_loop(self, k: int):
        """Jit the K-step fused loop: decode → sample → cache-write →
        bookkeeping inside one ``lax.scan``, emitting (tokens (k, b),
        emitted-codes (k, b) int32 — EMIT_NONE/TOKEN/FAULT) plus the
        advanced cache/state.

        Two robustness legs ride inside the body at zero marginal sync:

        * **Fault injection** — if the slot's armed ``fault_pos`` equals
          this step's sampling position, its logits row is overwritten
          with NaN/Inf (``fault_kind``).  Purely data-driven: arming a
          fault is a state write, never a recompile.
        * **Sentinel** — a per-slot non-finite reduce over the logits
          (catches injected faults AND real numeric escapes, e.g. a
          poisoned quantized cache decoding to inf).  A tripped slot
          emits EMIT_FAULT, keeps its pos/remaining/last_token frozen,
          and drops out of ``active`` inside the same body — so its
          cache writes stop mid-block and every surviving slot's stream
          is bit-identical to an uninjected run (rows are independent).
          The host sees the code in the SAME emitted array it already
          syncs once per block: detection costs no extra transfer."""
        model = self.model
        temp, top_k, max_seq = self.temperature, self.top_k, self.max_seq
        # mesh-native: decode leaves logits vocab-sharded over 'model'
        # (the unembed placement); the sample point is the loop's ONE
        # all-gather, after which tokens and bookkeeping are replicated
        logits_sh = self._sh["logits"] if self.mesh is not None else None

        def loop(params, cache, state, key):
            def body(carry, _):
                cache, st = carry
                active = st["active"]
                logits, cache = model.decode_step(
                    params, cache, st["last_token"], st["pos"],
                    active=active)
                nxt = st["pos"] + 1
                hit = (active & (st["fault_kind"] > jnp.int32(0))
                       & (st["fault_pos"] == nxt))
                bad_val = jnp.where(
                    st["fault_kind"] == jnp.int32(fault_lib.FAULT_INF),
                    jnp.inf, jnp.nan).astype(logits.dtype)
                logits = jnp.where(hit[:, None], bad_val[:, None], logits)
                bad = active & jnp.any(~jnp.isfinite(logits), axis=-1)
                ok = active & ~bad
                tok = sample_tokens(logits, key, temp, top_k,
                                    slot_seed=st["seed"], pos=nxt,
                                    logits_sharding=logits_sh)
                tok = jnp.where(ok, tok, st["last_token"])
                new_pos = jnp.where(ok, nxt, st["pos"])
                new_rem = st["remaining"] - ok.astype(jnp.int32)
                finished = ok & ((new_rem <= 0)
                                 | (new_pos >= max_seq - 1))
                st = dict(st, pos=new_pos, remaining=new_rem,
                          last_token=tok, active=ok & ~finished,
                          fault_kind=jnp.where(bad, jnp.int32(0),
                                               st["fault_kind"]))
                emit = (ok.astype(jnp.int32)
                        + jnp.int32(EMIT_FAULT) * bad.astype(jnp.int32))
                return (cache, st), (tok, emit)

            (cache, state), (toks, emitted) = jax.lax.scan(
                body, (cache, state), xs=None, length=k)
            return cache, state, toks, emitted

        if self.mesh is None:
            return jax.jit(loop)
        return jax.jit(loop, out_shardings=(
            self._sh["cache"], self._sh["state"],
            self._sh["replicated"], self._sh["replicated"]))

    # -- speculative decode --------------------------------------------- #
    def _make_spec_loop(self, n_blocks: int):
        """Jit the speculative fused loop: ``n_blocks`` draft→verify→
        commit blocks in one dispatch, each covering s = draft_tokens+1
        token positions.  Emits (tokens, emitted-codes) reshaped to
        (n_blocks*s, b) so :meth:`_harvest` consumes them exactly like
        the non-speculative loop's (k, b) outputs.

        Output equivalence is by construction, not by luck: the verify
        pass re-scores every drafted position with decode-bit-identical
        logits (``lm_verify_chunk``), the TRUE tokens are sampled from
        those logits with the same per-(request, position) key folds the
        non-speculative loop uses, and drafts only decide how many of
        those true tokens are valid this block: e = min(#leading draft
        matches + 1, remaining, max_seq-1-pos).  Accepted prefixes
        commit through the quantized cache-write path; rejected verify
        rows are simply never written (the target cache needs no
        rollback — only the eagerly-written draft-model cache does).

        Fault semantics match the non-speculative loop at token
        granularity: an armed fault poisons the verify logits row whose
        sampling position equals ``fault_pos``; if that row lands inside
        the accepted prefix, acceptance truncates there, EMIT_FAULT is
        emitted after the survivors, and the slot drops out of
        ``active`` (its partially-written block is discarded with the
        slot at the block-boundary ``clear_slot``)."""
        model, spec = self.model, self.spec
        temp, top_k, max_seq = self.temperature, self.top_k, self.max_seq
        D = spec.draft_tokens
        s = D + 1
        logits_sh = self._sh["logits"] if self.mesh is not None else None
        use_draft_model = self._draft_cache is not None
        dmodel = spec.draft_model

        def block(params, cache, st, key, dparams, dcache):
            active = st["active"]
            P = st["pos"]
            # 1. propose D drafts
            if spec.draft_fn is not None:
                drafts = spec.draft_fn(st)
            elif use_draft_model:
                def dstep(carry, _):
                    dc, tok, dpos = carry
                    dlogits, dc = dmodel.decode_step(
                        dparams, dc, tok, dpos, active=active)
                    ntok = jnp.argmax(dlogits, axis=-1).astype(jnp.int32)
                    return (dc, ntok, dpos + 1), ntok
                (dcache, _, _), drafts_t = jax.lax.scan(
                    dstep, (dcache, st["last_token"], P), xs=None,
                    length=D)
                drafts = drafts_t.transpose(1, 0)
            else:
                drafts = spec_lib.ngram_draft(
                    st["spec_hist"], st["spec_ngram"], D)
            # 2. verify: decode-exact logits for all s rows at once
            tokens = jnp.concatenate(
                [st["last_token"][:, None], drafts], axis=1)
            positions = (P[:, None]
                         + jnp.arange(s, dtype=jnp.int32)[None, :])
            logits, info = model.verify_chunk(
                params, cache, tokens, positions)
            # 3. armed logits fault: poison the row whose SAMPLING
            # position matches fault_pos (same trigger rule as the
            # non-speculative body, vectorized over the block)
            q_pos = positions + 1
            hit = (active[:, None]
                   & (st["fault_kind"][:, None] > jnp.int32(0))
                   & (st["fault_pos"][:, None] == q_pos))
            bad_val = jnp.where(
                st["fault_kind"] == jnp.int32(fault_lib.FAULT_INF),
                jnp.inf, jnp.nan).astype(logits.dtype)
            logits = jnp.where(hit[:, :, None], bad_val[:, None, None],
                               logits)
            # 4. sample the TRUE tokens (drafts never enter the stream)
            toks = sample_tokens_chunk(logits, key, temp, top_k,
                                       slot_seed=st["seed"], pos=q_pos,
                                       logits_sharding=logits_sh)
            # 5. acceptance: leading drafts that matched, plus the bonus
            # token sampled past the last match
            match = (drafts == toks[:, :D]).astype(jnp.int32)
            m = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
            e0 = jnp.minimum(m + 1, st["remaining"])
            e0 = jnp.minimum(e0, jnp.maximum(max_seq - 1 - P, 0))
            e0 = jnp.where(active, e0, 0)
            # sentinel: first non-finite verify row INSIDE the accepted
            # prefix truncates acceptance there and trips the fault
            bad_rows = (active[:, None]
                        & jnp.any(~jnp.isfinite(logits), axis=-1))
            first_bad = jnp.where(
                jnp.any(bad_rows, axis=1),
                jnp.argmax(bad_rows, axis=1).astype(jnp.int32),
                jnp.int32(s))
            fault = active & (first_bad < e0)
            e = jnp.where(fault, first_bad, e0)
            # 6. commit the accepted prefix (quantized cache-write path;
            # e = 0 rows are uniform no-ops)
            cache = model.commit_chunk(cache, info, positions, e)
            if use_draft_model:
                # the draft cache wrote eagerly during drafting: roll
                # back the rejected tail by pointer invalidation
                dpos = (P[:, None]
                        + jnp.arange(D, dtype=jnp.int32)[None, :])
                reject = (jnp.arange(D, dtype=jnp.int32)[None, :]
                          >= e[:, None])
                dcache = dmodel.rollback_chunk(dcache, dpos, reject)
            # 7. slot bookkeeping (identical rules, advanced by e)
            new_pos = P + e
            new_rem = st["remaining"] - e
            last = jnp.take_along_axis(
                toks, jnp.maximum(e - 1, 0)[:, None], axis=1)[:, 0]
            last = jnp.where(e > 0, last, st["last_token"])
            finished = (active & ~fault
                        & ((new_rem <= 0) | (new_pos >= max_seq - 1)))
            cols = jnp.arange(s, dtype=jnp.int32)[None, :]
            hist, table = spec_lib.ngram_update(
                st["spec_hist"], st["spec_ngram"], toks,
                cols < e[:, None])
            st = dict(st, pos=new_pos, remaining=new_rem,
                      last_token=last,
                      active=active & ~fault & ~finished,
                      fault_kind=jnp.where(fault, jnp.int32(0),
                                           st["fault_kind"]),
                      spec_hist=hist, spec_ngram=table,
                      spec_accept=st["spec_accept"] + e,
                      spec_blocks=(st["spec_blocks"]
                                   + active.astype(jnp.int32)))
            emit = jnp.where(cols < e[:, None], jnp.int32(EMIT_TOKEN),
                             jnp.int32(EMIT_NONE))
            emit = jnp.where(fault[:, None] & (cols == e[:, None]),
                             jnp.int32(EMIT_FAULT), emit)
            return cache, dcache, st, toks, emit

        def reshape_out(ys):
            # (n_blocks, b, s) -> (n_blocks * s, b): block-major rows,
            # the exact layout _harvest's host loop already consumes
            return ys.transpose(0, 2, 1).reshape(n_blocks * s, -1)

        if use_draft_model:
            def loop(params, cache, state, key, dparams, dcache):
                def body(carry, _):
                    cache, st, dc = carry
                    cache, dc, st, toks, emit = block(
                        params, cache, st, key, dparams, dc)
                    return (cache, st, dc), (toks, emit)
                (cache, state, dcache), (toks, emitted) = jax.lax.scan(
                    body, (cache, state, dcache), xs=None,
                    length=n_blocks)
                return (cache, state, reshape_out(toks),
                        reshape_out(emitted), dcache)
            return jax.jit(loop)

        def loop(params, cache, state, key):
            def body(carry, _):
                cache, st = carry
                cache, _, st, toks, emit = block(
                    params, cache, st, key, None, None)
                return (cache, st), (toks, emit)
            (cache, state), (toks, emitted) = jax.lax.scan(
                body, (cache, state), xs=None, length=n_blocks)
            return cache, state, reshape_out(toks), reshape_out(emitted)

        if self.mesh is None:
            return jax.jit(loop)
        return jax.jit(loop, out_shardings=(
            self._sh["cache"], self._sh["state"],
            self._sh["replicated"], self._sh["replicated"]))

    def _any_active(self) -> bool:
        return any(r is not None for r in self.slot_req)

    def _max_remaining(self) -> int:
        """Largest token budget left among in-flight slots (host-known:
        max_new_tokens minus tokens already emitted).  run() caps the
        fused block with this so the tail dispatch runs exactly the
        iterations it needs — without it, finishing a 23-token request
        with K=16 blocks would burn 9 fully-masked (but fully-costed)
        scan iterations."""
        rem = 0
        for slot, req in enumerate(self.slot_req):
            if req is not None:
                rem = max(rem,
                          req.max_new_tokens - len(self.out_tokens[slot]))
        return max(rem, 1)

    def _finish(self, slot: int, status: str = "ok") -> None:
        req = self.slot_req[slot]
        self.results.append(GenerationResult(
            req.request_id, req.prompt, self.out_tokens[slot],
            status=status, submit_t=req.submit_t,
            first_token_t=req.first_token_t, finish_t=self._now(),
            admit_t=req.admit_t))
        self.slot_req[slot] = None

    def _finish_unadmitted(self, req: _Request, status: str) -> None:
        """Account a request that never reached a slot (shed by the
        admission policy, cancelled while queued, or deadline-expired
        before prefill): zero tokens, terminal status."""
        self.results.append(GenerationResult(
            req.request_id, req.prompt, [], status=status,
            submit_t=req.submit_t, finish_t=self._now()))

    def _dispatch(self, k: int) -> int:
        """One fused dispatch of K decode steps + one host sync for its
        K×batch tokens.  Fault recovery happens in :meth:`_harvest`, at
        the block boundary: a slot whose emitted codes contain
        EMIT_FAULT keeps the tokens it emitted before the sentinel
        tripped, finishes as ``status="faulted"``, and its pool region
        is re-initialized through the existing ``clear_slot`` eviction
        path — the next admission reuses the slot as if the fault never
        happened.  Returns the decode-step budget actually spent (k
        here; the speculative leg rounds up to whole blocks)."""
        if self.spec is not None:
            return self._dispatch_spec(k)
        fn = self._loops.get(k)
        if fn is None:
            fn = self._loops[k] = self._make_decode_loop(k)
        with _span(SPAN_DECODE, k=k):
            self.cache, self.state, toks, emitted = fn(
                self.params, self.cache, self.state, self._sample_key)
        self._harvest(toks, emitted)
        return k

    def _dispatch_spec(self, k: int) -> int:
        """Speculative dispatch covering >= k token positions:
        ceil(k / (draft_tokens+1)) fused draft→verify→commit blocks in
        one launch, then the same one-sync harvest."""
        s = self.spec.draft_tokens + 1
        n_blocks = max(1, -(-k // s))
        fn = self._spec_loops.get(n_blocks)
        if fn is None:
            fn = self._spec_loops[n_blocks] = self._make_spec_loop(
                n_blocks)
        with _span(SPAN_DECODE, k=k):
            if self._draft_cache is not None:
                (self.cache, self.state, toks, emitted,
                 self._draft_cache) = fn(
                    self.params, self.cache, self.state, self._sample_key,
                    self._draft_params, self._draft_cache)
            else:
                self.cache, self.state, toks, emitted = fn(
                    self.params, self.cache, self.state, self._sample_key)
        codes = self._harvest(toks, emitted)
        # engine-lifetime acceptance accounting, from the SAME synced
        # array: a (block, slot) cell counts as a run block iff any code
        # is non-NONE there (the slot was active entering the block)
        per_block = codes.reshape(n_blocks, s, -1)
        self._spec_tokens += int((codes == EMIT_TOKEN).sum())
        self._spec_blocks += int(
            (per_block != EMIT_NONE).any(axis=1).sum())
        return n_blocks * s

    def _harvest(self, toks, emitted) -> np.ndarray:
        """Block-boundary host pass shared by both loop flavours: three
        host reads -- the (rows, batch) tokens and emitted codes and the
        slots' ``active`` flags after the block -- then per-slot
        extend/finish/fault bookkeeping.  Returns the host codes."""
        with _span(SPAN_HARVEST):
            toks = self._host_read(toks)       # (rows, b); the first read
            emitted = self._host_read(emitted)  # waits for the block
            active_after = self._host_read(self.state["active"])
            self._dispatches += 1
            for slot in range(self.batch):
                if self.slot_req[slot] is None:
                    continue
                codes = emitted[:, slot]
                self.out_tokens[slot].extend(
                    int(t) for t, e in zip(toks[:, slot], codes)
                    if e == EMIT_TOKEN)
                if (codes == EMIT_FAULT).any():
                    self._finish(slot, status="faulted")
                    self.cache = self._clear_slot_fn(self.cache,
                                                     jnp.int32(slot))
                    if self._draft_cache is not None:
                        self._draft_cache = self._draft_clear_fn(
                            self._draft_cache, jnp.int32(slot))
                elif not active_after[slot]:
                    self._finish(slot)
                else:
                    self._slot_progress[slot] = (len(self.out_tokens[slot]),
                                                 self._dispatches)
            if self._deadlines_live:
                self._expire_inflight()
            return emitted

    def spec_report(self) -> Dict:
        """Engine-lifetime speculation accounting (host totals; the
        per-slot in-flight view lives in ``state['spec_accept']`` /
        ``state['spec_blocks']``).  ``mean_accepted_len`` is tokens
        committed per run block — the paper-style acceptance length
        (1.0 = no draft ever accepted, draft_tokens+1 = every block
        fully accepted)."""
        blocks = self._spec_blocks
        return {
            "enabled": self.spec is not None,
            "draft_tokens": (0 if self.spec is None
                             else self.spec.draft_tokens),
            "blocks": blocks,
            "accepted_tokens": self._spec_tokens,
            "mean_accepted_len": (self._spec_tokens / blocks
                                  if blocks else 0.0),
        }

    def _expire_inflight(self) -> None:
        """Cancel in-flight requests whose deadline passed: one jitted
        cancel state-write each, partial tokens delivered as
        ``deadline_exceeded``."""
        now = self._now()
        for slot, req in enumerate(self.slot_req):
            if (req is not None and req.deadline_s is not None
                    and now >= req.deadline_s):
                self.state = self._cancel_fn(self.state, jnp.int32(slot))
                self._finish(slot, status="deadline_exceeded")

    # -- cancellation / fault injection ---------------------------------- #
    def _slot_of(self, request_id: int) -> Tuple[int, _Request]:
        for slot, req in enumerate(self.slot_req):
            if req is not None and req.request_id == request_id:
                return slot, req
        raise KeyError(f"request {request_id} is not in flight")

    def cancel(self, request_id: int, status: str = "shed") -> bool:
        """Cancel a request wherever it lives.  Queued: removed without
        ever touching the device.  In flight: one jitted cancel
        state-write deactivates the slot (same compile-once shape as
        admission) and the partial tokens are delivered under
        ``status``.  Returns False when the id is unknown or already
        finished."""
        if status not in STATUSES:
            raise ValueError(f"status {status!r} not in {STATUSES}")
        req = self.queue.remove(request_id)
        if req is not None:
            self._finish_unadmitted(req, status)
            return True
        try:
            slot, _ = self._slot_of(request_id)
        except KeyError:
            return False
        self.state = self._cancel_fn(self.state, jnp.int32(slot))
        self._finish(slot, status=status)
        return True

    def inject_fault(self, request_id: int, kind: str = "logits_nan",
                     delay: int = 0, leaf: str = "k_s",
                     xor: int = 0xFF) -> None:
        """Arm a fault against an in-flight request (testing/chaos API;
        see ``repro.serve.faults`` for the taxonomy and which kinds the
        sentinel can detect).

        Logits kinds (``logits_nan``/``logits_inf``) arm the in-loop
        injector: the fault fires when the slot samples its
        ``delay``-th next token (0 = the first token of the next
        dispatch).  Cache kinds (``e8m0_overflow``/``kv_bitflip``/
        ``state_inf``) poison the slot's cache region immediately via
        one jitted pure cache-write; ``e8m0_overflow``/``state_inf``
        decode to inf by construction so the sentinel sees them on the
        next decode step, while ``kv_bitflip`` usually decodes to wrong
        -but-finite values the sentinel cannot see (the documented
        silent-corruption gap)."""
        slot, req = self._slot_of(request_id)
        if kind in fault_lib.LOGITS_FAULTS:
            if delay < 0:
                raise ValueError("delay must be >= 0")
            pos = req.trunk_len + len(self.out_tokens[slot]) + delay
            self.state = self._fault_arm_fn(
                self.state, jnp.int32(slot), jnp.int32(pos),
                jnp.int32(fault_lib.LOGITS_FAULTS[kind]))
            return
        if kind not in fault_lib.CACHE_POISONERS:
            raise ValueError(
                f"unknown fault kind {kind!r}; choose from "
                f"{fault_lib.FAULT_KINDS}")
        key = (kind, leaf, xor) if kind == "kv_bitflip" else (kind,)
        fn = self._fault_cache_fns.get(key)
        if fn is None:
            if kind == "kv_bitflip":
                base = functools.partial(fault_lib.flip_kv_bytes,
                                         leaf=leaf, xor=xor)
            else:
                base = fault_lib.CACHE_POISONERS[kind]
            fn = self._fault_cache_fns[key] = self._jit(
                base, self._cache_sh)
        self.cache = fn(self.cache, jnp.int32(slot))

    # -- accounting / watchdog ------------------------------------------- #
    def accounting(self) -> Dict[str, int]:
        """Exact request accounting.  ``balanced`` asserts the shed
        identity: every submitted request is either still pending
        (queued/in-flight) or in exactly one terminal status —
        submitted = ok + truncated + shed + deadline_exceeded + faulted
        + in_flight + queued."""
        by_status = {s: 0 for s in STATUSES}
        for r in self.results:
            by_status[r.status] += 1
        in_flight = sum(r is not None for r in self.slot_req)
        queued = len(self.queue)
        done = sum(by_status.values())
        return dict(by_status, submitted=self._submitted,
                    completed=by_status["ok"] + by_status["truncated"],
                    in_flight=in_flight, queued=queued,
                    balanced=(self._submitted
                              == done + in_flight + queued))

    def watchdog_report(self) -> Dict:
        """Host/device slot reconciliation (diagnostic path — a handful
        of host reads, never called inside a timed region).  Flags:
        device-active slots with no host-side tenant (orphans), host
        tenants whose device slot went inactive without being finished,
        negative ``remaining`` / out-of-range ``pos`` bookkeeping, a
        device ``remaining`` that disagrees with the host token count,
        and slots that stayed active across dispatches without emitting
        (stuck — e.g. a scheduler bug starving the slot's writes)."""
        active = self._host_read(self.state["active"])
        pos = self._host_read(self.state["pos"])
        remaining = self._host_read(self.state["remaining"])
        findings: List[str] = []
        for slot in range(self.batch):
            req = self.slot_req[slot]
            if req is None:
                if active[slot]:
                    findings.append(
                        f"slot {slot}: device-active with no host "
                        f"request (orphaned slot)")
                continue
            if not active[slot]:
                findings.append(
                    f"slot {slot}: host request {req.request_id} on an "
                    f"inactive device slot (lost finish)")
            if remaining[slot] < 0:
                findings.append(
                    f"slot {slot}: remaining={int(remaining[slot])} < 0")
            if pos[slot] >= self.max_seq:
                findings.append(
                    f"slot {slot}: pos={int(pos[slot])} >= max_seq "
                    f"{self.max_seq}")
            host_rem = req.max_new_tokens - len(self.out_tokens[slot])
            if active[slot] and int(remaining[slot]) != host_rem:
                findings.append(
                    f"slot {slot}: device remaining="
                    f"{int(remaining[slot])} != host budget {host_rem}")
            count, seen = self._slot_progress[slot]
            if (active[slot] and self._dispatches - seen >= 3
                    and len(self.out_tokens[slot]) == count):
                findings.append(
                    f"slot {slot}: stuck — no tokens emitted for "
                    f"{self._dispatches - seen} dispatches")
        return {"ok": not findings, "findings": findings,
                "dispatches": self._dispatches}

    def decode_loop(self, k: Optional[int] = None) -> None:
        """Admit from the queue, then run K fused decode steps in one
        dispatch (K = ``decode_block`` by default)."""
        self._admit()
        if self._any_active():
            self._dispatch(k or self.decode_block)

    def step(self) -> None:
        """One pooled decode step — the per-token dispatch pattern (one
        launch + one host sync per generated token).  Kept as the
        measurable baseline; :meth:`run` uses the fused loop."""
        self.decode_loop(1)

    # -- driver --------------------------------------------------------- #
    def run(self, max_steps: int = 1000) -> List[GenerationResult]:
        """Serve until queue and pool drain or ``max_steps`` decode steps
        have been spent.  On budget exhaustion, in-flight requests are
        FLUSHED as partial results (``status="truncated"``) instead of
        being silently dropped.

        A non-admittable queue state (non-empty queue, nothing active,
        and an admission pass that neither admitted, expired, nor shed
        anything) raises instead of spinning: the old bare ``continue``
        could loop forever without spending a step."""
        steps = 0
        while steps < max_steps:
            before = (len(self.queue), len(self.results))
            self._admit()
            if not self._any_active():
                if not self.queue:
                    break
                if (len(self.queue), len(self.results)) == before:
                    raise RuntimeError(
                        f"run() stalled: {len(self.queue)} queued "
                        f"request(s), no active slots, and an admission "
                        f"pass made no progress — scheduler/admission "
                        f"bug (would previously spin silently)")
                continue
            k = min(self.decode_block, max_steps - steps,
                    self._max_remaining())
            steps += self._dispatch(k)
        if self._any_active():
            # budget hit mid-generation: flush partials and deactivate
            # their device slots so a later run() cannot advance them
            for slot in range(self.batch):
                if self.slot_req[slot] is not None:
                    self._finish(slot, status="truncated")
            self.state = dict(
                self.state,
                active=jnp.zeros_like(self.state["active"]))
        return sorted(self.results, key=lambda r: r.request_id)
