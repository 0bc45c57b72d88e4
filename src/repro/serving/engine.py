"""Serving engines: continuous batching over per-slot request state.

``ServingEngine`` is the production path. It owns a fixed pool of
``batch_slots`` decode slots sharing one device-resident KV cache; requests
are admitted into free slots as others finish (continuous batching), so a
long generation never stalls the short ones behind it. Prompt lengths are
bucketed to a small set of power-of-two shapes, bounding prefill
recompilation to ``len(buckets)`` variants regardless of traffic. The decode
inner step is one fused jitted call — sample → cache-append →
done-detection all on device — and the Python loop performs a single small
host sync per round (the (B,) active mask) for EOS/slot management; logits
never leave the device.

With ``max_decode_steps=K`` the engine goes further: pure-decode rounds
``lax.scan`` up to K fused steps inside one jit, paying one dispatch and
one host sync per K generated tokens (multi-step decode). Everything the
step needs — sampling keys folded from the carried ``(request_id, steps)``,
per-slot positions, the EOS/budget active mask — already lives in the
on-device carry, so the scan is exactly K repetitions of the single-step
program and outputs are token-for-token identical at every K. The
scheduler collapses the horizon to 1 whenever prefill work is pending (or
a request was just admitted), preserving chunked-prefill TTFT behavior,
and caps it by the smallest active slot's remaining budget. Paged slots
get a look-ahead block reservation (``reserve_lookahead`` →
``begin_slot``) before each scan so every in-scan append lands in an
allocated block.

With a ``draft_model`` and ``speculative_tokens=k`` the engine decodes
**speculatively**: a small draft LM (the natural choice is the cascade's
edge model — the ACE edge/cloud split is exactly a draft/verify pair)
proposes k tokens per slot autoregressively on its own ring cache, and
the target verifies all of them in *one* chunked decode dispatch —
paying one target dispatch and one host sync per ``1 + accepted`` tokens
instead of per token. Verification is key-coupled (see ``_spec_impl``):
draft and target sample through the same per-(request, step) folded
keys, a proposal is accepted iff it equals the token the target samples
there, so speculative streams are **token-for-token identical to the
non-speculative engine at every temperature** — acceptance rate is the
only thing draft quality affects. The scheduler picks the draft depth
per plan beside its decode horizon, collapsing to non-speculative while
prefill work is pending or while the acceptance EWMA says drafting
loses, and the paged look-ahead reservation covers the k-token worst
case so a verify append never faults mid-dispatch.

Scheduling policy lives in ``repro.serving.scheduler``: each step the
``Scheduler`` composes a mixed batch under a token budget — decode tokens
for the active slots plus prompt *chunks* for admitting requests — and the
engine merely executes the plan. With ``chunk_tokens=None`` (default) the
plan degenerates to the legacy admit-whole-bucket-then-decode behavior;
with chunking enabled a long prompt prefills incrementally across steps
(``LM.prefill_chunk``), so a burst of arrivals no longer stalls in-flight
decodes for a monolithic prefill. Either way outputs are token-exact.

Prompts are right-padded to their bucket. With the ring cache this is
*exact*: pad entries sit at positions ≥ the prompt length, causal masking
hides them until the decode stream overwrites their ring slot at that same
position, so bucketing never changes a single output token. Chunk shapes
are bucketed the same way, and chunk pads are masked out of the cache
entirely (``valid``), so chunking is exact too.

The KV cache itself is pluggable (``repro.serving.kv_cache``): admission
grants a slot *plus* whatever device memory the backend needs for it. The
``ring`` backend (default) pins a ``max_seq_len`` cache line per slot; the
``paged`` backend reserves ``ceil((prompt + budget) / block_size)`` pool
blocks per request and returns them at completion, so concurrency is
bounded by live tokens rather than worst-case sequence length — and, with
chunked prefill, requests sharing a full-block prompt prefix share the
physical blocks (refcounted, copy-on-write) and skip recomputing them.

Sampling keys are derived per request (``request_id`` × decode step), so
temperature > 0 outputs are a pure function of the request: co-scheduling,
admission order and chunking never change a sampled stream.

Scheduling is **SLO-aware**: ``submit`` takes a priority class and an
optional relative deadline, the scheduler serves classes strictly
(class, then earliest deadline, then FIFO — see
``scheduler.request_rank``), and when a higher-class request is blocked on
resources the engine **preempts** the worst-ranked active slot: its decode
state (generated tokens, step counter, next-sample logits) is
checkpointed on the host, its cache is swapped out
(``PagedCache.swap_out`` returns the blocks to the pool) or simply freed
(ring — the K/V is rebuilt at resume by re-prefilling prompt + generated
tokens), and the request re-enters the queue to resume later
**token-for-token** (sampling keys fold the restored step counter; the
saved logits make the first resumed token bit-exact). First-admission
timing is sticky across preemption, so ``admit_s``/``ttft_s`` keep
measuring the request's real service experience.

The stack is **chaos-hardened**: with a ``fault_plan``
(``repro.serving.faults.FaultPlan``) armed, named seams — the decode
dispatch, both KV-swap directions, pool admission, mid-flight
cancellation — inject deterministic faults, and recovery reuses the
preemption machinery: faulted slots roll back to their host checkpoint
and requeue with bounded step-indexed exponential backoff; a request
exceeding ``max_retries`` is quarantined with terminal status ``failed``
instead of wedging the loop. Every request ends in exactly one terminal
status (``done``/``failed``/``rejected``/``cancelled``) with a
machine-readable ``failure_reason``; oversized requests are rejected at
admission rather than raising, ``cancel()`` works in every phase, and
``metrics()`` snapshots the full health picture for
``core.monitoring``. Survivors of any fault schedule finish
token-for-token identical to the fault-free run (``tests/test_faults.py``).

``DrainBatchEngine`` preserves the previous drain-the-queue batcher (pad
the batch to its longest prompt, run everyone for the longest budget,
round-trip logits to the host each token) as the measured baseline for
``benchmarks/bench_serving.py``.
"""
from __future__ import annotations

import collections
import dataclasses
import os
import pathlib
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.io import (json_leaf, json_unleaf,
                                 load_checkpoint_tree, save_checkpoint)
from repro.models.model import LM
from repro.serving.faults import FaultError, FaultPlan
from repro.serving.kv_cache import (RingCache, RingLayout, make_backend,
                                    resolve_swap_caches)
from repro.serving import tracing
from repro.serving.sharding import (assert_cache_placement, cache_shardings,
                                    place_params, serving_rules)
from repro.serving.sampler import (accepted_prefix_length, request_keys,
                                   sample_logits_batch, sample_logits_keyed)
from repro.serving.scheduler import (MONOLITHIC, PrefillProgress, Scheduler,
                                     bucket_for, prompt_buckets,
                                     request_rank)
from repro.utils.tree import flat_paths


@dataclasses.dataclass
class Request:
    request_id: int
    prompt: np.ndarray           # (S_prompt,) int32
    max_new_tokens: int = 16
    temperature: float = 0.0
    priority: int = 0            # SLO class: higher = more critical
    deadline_s: Optional[float] = None   # relative SLO deadline (from submit)
    output: Optional[np.ndarray] = None
    submit_s: float = 0.0        # wall-clock at submit()
    admit_s: float = 0.0         # wall-clock at *first* slot grant (sticky:
    #                              preempt/resume never restamps it)
    finish_s: float = 0.0        # wall-clock at completion
    latency_s: float = 0.0       # finish - submit (queue + service)
    ttft_s: float = 0.0          # submit -> first generated token exists
    preemptions: int = 0         # times swapped out under SLO pressure
    resume: Optional["_ResumeState"] = dataclasses.field(
        default=None, repr=False)     # checkpoint while preempted
    # terminal disposition: "queued"/"active" while live, then exactly one
    # of done | failed (retry budget exhausted) | rejected (admission
    # refused it) | cancelled. ``failure_reason`` is machine-readable: a
    # code, optionally ": detail" for humans.
    status: str = "queued"
    failure_reason: Optional[str] = None
    retries: int = 0             # fault-triggered rollbacks so far
    last_fault: Optional[str] = None  # seam of the most recent fault
    downgraded: bool = False     # deadline stripped by admission control
    not_before_step: int = 0     # backoff: ineligible before this step
    fault_s: float = 0.0         # wall-clock of last fault requeue (recovery
    #                              latency = next slot grant - fault_s)


@dataclasses.dataclass
class _ResumeState:
    """Everything a preempted request needs to resume token-for-token:
    the host-side decode checkpoint (generated tokens, step count, the
    logits the next sample reads) plus, on the swap path, the backend's
    opaque K/V checkpoint. ``kv`` is None on the recompute path — the
    engine rebuilds the cache by re-prefilling prompt + generated tokens
    (position-masked attention makes the rebuilt K/V identical, and the
    saved ``last`` logits are restored verbatim, so the next sampled token
    is bit-exact either way)."""
    steps: int
    tokens: np.ndarray           # (steps,) generated so far
    last: np.ndarray             # (V,) f32 logits to sample the next token
    kv: Optional[object] = None  # PagedCache.swap_out checkpoint


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 1).bit_length()


def _has_windowed_blocks(lm: LM) -> bool:
    return any(bdef.window is not None
               for stage in lm.cfg.stages for bdef in stage.blocks)


def validate_prompt(prompt: np.ndarray, max_new_tokens: int,
                    max_seq_len: int, truncate: bool) -> np.ndarray:
    """Shared submit-time guard: prompt + budget must fit the cache.

    Historically an over-long prompt fell into the top bucket and silently
    relied on ring wraparound (the oldest tokens were overwritten mid-
    prefill — wrong outputs, no error). Now the engines either raise here
    with an actionable message or, when ``truncate`` is set, explicitly keep
    the trailing ``max_seq_len - max_new_tokens`` prompt tokens."""
    prompt = np.asarray(prompt, np.int32)
    assert prompt.ndim == 1
    room = max_seq_len - max_new_tokens
    if room <= 0:
        raise ValueError(
            f"max_new_tokens ({max_new_tokens}) leaves no room for a prompt "
            f"within max_seq_len ({max_seq_len})")
    if len(prompt) > room:
        if not truncate:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new_tokens})"
                f" exceeds max_seq_len ({max_seq_len}); the output buffer"
                f" and cache are sized for max_seq_len — shorten the prompt,"
                f" raise max_seq_len, or construct the engine with"
                f" truncate_prompts=True to keep the prompt tail")
        prompt = prompt[-room:]
    return prompt


# one fixed path inside the checkout: the directory is part of the cache's
# key, so a path that moved between runs would never hit
DEFAULT_COMPILE_CACHE = str(
    pathlib.Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Arm JAX's persistent on-disk executable cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the cache
    there and no directory is set here; otherwise it goes to
    ``DEFAULT_COMPILE_CACHE``. ``warm_compile`` pre-runs every chunk bucket
    × scan horizon × backend variant per process, so a second process (a
    supervised restart-from-snapshot, a rerun) replays the whole executable
    family from disk instead of recompiling it. Thresholds are dropped to
    zero so small executables are cached too, not just the multi-second
    compiles."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(DEFAULT_COMPILE_CACHE, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir


class ServingEngine:
    """Continuous-batching autoregressive serving."""

    def __init__(self, lm: LM, params, *, batch_slots: int = 8,
                 max_seq_len: int = 512, seed: int = 0,
                 eos_id: Optional[int] = None, min_bucket: int = 16,
                 cache_backend="ring", block_size: int = 16,
                 num_pool_blocks: Optional[int] = None,
                 truncate_prompts: bool = False,
                 chunk_tokens: Optional[int] = None,
                 token_budget: Optional[int] = None,
                 prefix_sharing: bool = True,
                 max_decode_steps: int = 1,
                 preempt_mode: str = "auto",
                 fault_plan: Optional[FaultPlan] = None,
                 max_retries: int = 3,
                 backoff_base_steps: int = 1,
                 backoff_cap_steps: int = 8,
                 admission_policy: Optional[str] = None,
                 draft_model: Optional[LM] = None,
                 draft_params=None,
                 speculative_tokens: int = 0,
                 mesh=None, rules=None):
        if lm.cfg.frontend.kind == "audio":
            raise NotImplementedError("engine serves text-token streams")
        self.lm = lm
        self.params = params
        # mesh-aware serving: with a mesh, params commit to the decode-mode
        # NamedShardings (attention/KV heads, MLP, vocab on 'model') and
        # every model call below runs under the logical-axis rule context,
        # so GSPMD partitions the jitted step family across the mesh. All
        # scheduling and allocator state stays host-global. mesh=None takes
        # every one of today's single-device code paths unchanged (the
        # rules context is a literal no-op and no jit signature changes).
        self.mesh = mesh
        self.rules = None
        if mesh is not None:
            self.rules = dict(rules) if rules is not None \
                else serving_rules(mesh)
            self.params = place_params(mesh, lm, self.params)
        self.batch_slots = batch_slots
        self.max_seq_len = max_seq_len
        self.eos_id = eos_id
        self.truncate_prompts = truncate_prompts
        self.buckets = prompt_buckets(max_seq_len, min_bucket)
        self._windowed = _has_windowed_blocks(lm)
        self._queue: List[Request] = []
        self._next_id = 0
        self._base_key = jax.random.PRNGKey(seed)
        # serving state (step() advances it; run() drains it)
        self._slots: Dict[int, Request] = {}
        self._free: List[int] = list(range(batch_slots))
        self._prefilling: Dict[int, PrefillProgress] = \
            collections.OrderedDict()
        self._done: Dict[int, Request] = {}
        # host-side mirror of each live slot's completed decode steps: a
        # slot active at a sync advanced exactly the scanned step count, so
        # this is exact for live slots and gives the scheduler its budget
        # headroom (and the look-ahead reservation its positions) without
        # an extra device pull
        self._scanned: Dict[int, int] = {}
        # counters: decode_steps counts *token* rounds (a K-scan adds K),
        # host_syncs counts active-mask transfers (a K-scan adds 1)
        self.decode_steps = 0
        self.host_syncs = 0
        self.generated_tokens = 0
        self.peak_active_slots = 0
        self.prefill_tokens_total = 0
        self.prefill_tokens_skipped = 0
        # scheduled-vs-useful token-slot accounting (see ``occupancy``)
        self.planned_token_slots = 0
        self.useful_prefill_tokens = 0
        # SLO scheduling: engine-level preemption count (per-request counts
        # live on ``Request.preemptions``) and look-ahead reservation
        # dispatch count (coalesced: one per decode round with top-ups)
        self.preemptions = 0
        self.lookahead_dispatches = 0
        # fault tolerance: injected-fault recovery rolls affected slots back
        # to their last host checkpoint and requeues them with bounded
        # exponential backoff (measured in engine steps, so recovery is
        # deterministic under test); a request exceeding ``max_retries``
        # fault rollbacks is quarantined with terminal status "failed"
        # instead of wedging the drain loop
        self._faults = fault_plan
        self.max_retries = max_retries
        self.backoff_base_steps = backoff_base_steps
        self.backoff_cap_steps = backoff_cap_steps
        self._step_count = 0
        self.fault_recoveries = 0     # decode rounds rolled back
        self.retries_total = 0        # per-request retries, summed
        self.recovery_latencies: List[float] = []  # fault -> re-grant, s
        # durability: restore()s applied to this engine and watchdog-
        # escalated hang recoveries (note_hang); deferred swap-out D2H
        # transfers are parked here and materialized after the *next*
        # scheduler plan, so the copy overlaps host planning work
        self.restores = 0
        self.hang_recoveries = 0
        # wall time of the last warm_compile() (None until called): cold
        # process vs snapshot-restart with the persistent compile cache
        self.warm_compile_s: Optional[float] = None
        self._pending_swaps: List[object] = []
        self._status_counts = collections.Counter()  # terminal dispositions
        # per-step token tap (the gateway's streaming feed): when set, every
        # decode round's host sync is followed by a call with the round's
        # newly generated tokens, [(request_id, np.ndarray), ...]. Emission
        # is monotone per request — preemption/fault rollback checkpoints
        # every generated token, so a resumed stream continues exactly
        # where it stopped and a streamed token is never retracted
        self.on_tokens = None
        self._emitted: Dict[int, int] = {}     # rid -> tokens already tapped

        if chunk_tokens is not None:
            self._validate_chunk_mixers(chunk_tokens)
        self.backend = make_backend(
            cache_backend, lm, params, batch_slots=batch_slots,
            max_seq_len=max_seq_len, proto_len=self.buckets[0],
            block_size=block_size, num_blocks=num_pool_blocks,
            prefix_sharing=prefix_sharing)
        if chunk_tokens is not None:
            self._validate_chunk_layout()
        # speculative decoding: a draft LM proposes k tokens per slot on its
        # own lightweight ring cache; the target verifies all of them in one
        # chunked decode dispatch (see _spec_impl). speculative_tokens=0 (or
        # no draft model) leaves every code path below bit-identical to the
        # non-speculative engine.
        if speculative_tokens > 0 and draft_model is None:
            raise ValueError("speculative_tokens > 0 needs a draft_model")
        self.speculative = draft_model is not None and speculative_tokens > 0
        if self.speculative:
            if draft_params is None:
                raise ValueError("draft_model needs draft_params")
            if draft_model.cfg.frontend.kind == "audio":
                raise NotImplementedError(
                    "draft model must serve text-token streams")
            if draft_model.cfg.padded_vocab != lm.cfg.padded_vocab:
                raise ValueError(
                    f"draft vocab ({draft_model.cfg.padded_vocab}) must "
                    f"match the target's ({lm.cfg.padded_vocab}): "
                    f"verification compares token ids")
            bad = lm.chunk_incompatible_mixer()
            if bad is not None:
                raise NotImplementedError(
                    f"speculative verification is a multi-token chunk query;"
                    f" the target's {bad!r} mixer folds tokens sequentially "
                    f"— use speculative_tokens=0")
        self.scheduler = Scheduler(
            batch_slots=batch_slots, chunk_tokens=chunk_tokens,
            token_budget=token_budget, max_decode_steps=max_decode_steps,
            admission_policy=admission_policy,
            speculative_tokens=speculative_tokens if self.speculative else 0)
        # prefix sharing hashes prompt tokens at admission; only meaningful
        # with chunked install (monolithic prefill recomputes everything)
        self._admit_with_tokens = (
            self.scheduler.chunked
            and getattr(self.backend, "prefix_sharing", False))
        self._cache_state = self.backend.init()
        if mesh is not None:
            # commit the KV pool to the mesh (K/V leaves split on the
            # KV-head dim, tables/positions replicated) and tell the
            # backend so its per-device byte accounting matches
            self._cache_state = jax.device_put(
                self._cache_state, cache_shardings(mesh, self._cache_state))
            self.backend.note_placement(mesh)
        b, v = batch_slots, lm.cfg.padded_vocab
        self._state = {
            "last": jnp.zeros((b, v), jnp.float32),     # logits to sample next
            "pos": jnp.zeros((b,), jnp.int32),
            "steps": jnp.zeros((b,), jnp.int32),
            "budget": jnp.zeros((b,), jnp.int32),
            "temp": jnp.zeros((b,), jnp.float32),
            "rid": jnp.zeros((b,), jnp.int32),
            "active": jnp.zeros((b,), jnp.bool_),
            "out": jnp.zeros((b, max_seq_len), jnp.int32),
        }
        # cache/state buffers are engine-owned and reassigned from every
        # call's output: donate them so XLA updates in place instead of
        # copying the whole KV cache per step/chunk/admission
        self._admit_fn = jax.jit(self._admit_impl,
                                 donate_argnums=(1, 2))  # retraces per bucket
        self._step_fn = jax.jit(self._step_impl, donate_argnums=(1, 2))
        self._scan_fn = jax.jit(self._scan_impl, donate_argnums=(1, 2),
                                static_argnums=(4,))     # per horizon K
        self._chunk_fn = jax.jit(self._chunk_impl, donate_argnums=(1, 2),
                                 static_argnums=(12,))   # per (bucket, ctx)
        self._begin_fn = jax.jit(self.backend.begin_slot, donate_argnums=0)
        if hasattr(self.backend, "begin_slots"):
            # coalesced look-ahead reservation: one device update for every
            # slot crossing a block boundary in the same plan (inputs are
            # padded to batch_slots, so this compiles exactly once)
            self._begin_many_fn = jax.jit(self.backend.begin_slots,
                                          donate_argnums=0)
        if hasattr(self.backend, "copy_block"):
            self._copy_fn = jax.jit(self.backend.copy_block, donate_argnums=0)
        if preempt_mode not in ("auto", "swap", "recompute"):
            raise ValueError(f"preempt_mode must be 'auto', 'swap' or "
                             f"'recompute' (got {preempt_mode!r})")
        if preempt_mode == "swap" and not hasattr(self.backend, "swap_out"):
            raise ValueError(
                "preempt_mode='swap' needs a backend with swap_out/swap_in "
                "(paged); the ring backend resumes by recompute")
        self._preempt_swap = (preempt_mode in ("auto", "swap")
                              and hasattr(self.backend, "swap_out"))
        # speculative accounting (zeroed even without a draft so metrics()
        # keeps a uniform shape): drafted = proposals issued (slots × k),
        # accepted = proposals the target kept, committed = accepted + the
        # anchor token every speculative round banks per slot
        self.spec_rounds = 0
        self.spec_slot_rounds = 0            # Σ active slots over spec rounds
        self.spec_drafted_tokens = 0
        self.spec_accepted_tokens = 0
        self.spec_committed_tokens = 0
        self.spec_fallbacks = 0              # draft-seam faults served plain
        self._spec_class: Dict[int, tuple] = {}  # priority -> (drafted, acc)
        if self.speculative:
            self.draft_lm = draft_model
            self.draft_params = draft_params
            self._draft_windowed = _has_windowed_blocks(draft_model)
            # the draft always rides a ring cache, whatever the target's
            # backend: it never pages, never swaps, never shares prefixes —
            # a fixed max_seq_len line per slot is its whole state
            self._draft_backend = RingCache(
                draft_model, draft_params, batch_slots=batch_slots,
                max_seq_len=max_seq_len, proto_len=self.buckets[0])
            self._draft_state = self._draft_backend.init()
            if mesh is not None:
                # the draft rides the same mesh: its params/ring shard by
                # the same decode rules (leaves whose dims don't divide
                # simply replicate). Draft numerics only steer acceptance —
                # key-coupled verification keeps outputs exact regardless.
                self.draft_params = place_params(mesh, draft_model,
                                                 self.draft_params)
                self._draft_state = jax.device_put(
                    self._draft_state,
                    cache_shardings(mesh, self._draft_state))
                self._draft_backend.note_placement(mesh)
            # slots whose draft cache missed tokens (generated by plain
            # decode rounds while speculation was collapsed): re-synced by
            # a draft prefill before the next speculative round reads them
            self._draft_dirty: set = set()
            self._spec_fn = jax.jit(self._spec_impl,
                                    donate_argnums=(2, 3, 4),
                                    static_argnums=(6,))  # per draft depth k
            self._draft_fill_fn = jax.jit(self._draft_fill_impl,
                                          donate_argnums=(1,))  # per bucket

    def _validate_chunk_mixers(self, chunk_tokens: int) -> None:
        if not (1 <= chunk_tokens <= self.max_seq_len):
            raise ValueError(f"chunk_tokens ({chunk_tokens}) must be in "
                             f"[1, max_seq_len={self.max_seq_len}]")
        bad = self.lm.chunk_incompatible_mixer()
        if bad is not None:
            raise NotImplementedError(
                f"chunked prefill needs attention mixers (got "
                f"{bad!r}); recurrent state folds tokens "
                f"sequentially — use chunk_tokens=None")

    def _validate_chunk_layout(self) -> None:
        if not isinstance(self.backend.layout, RingLayout):
            return
        for stage in self.lm.cfg.stages:
            for bdef in stage.blocks:
                if bdef.window is not None:
                    raise NotImplementedError(
                        "chunked prefill over windowed layers needs the "
                        "paged backend: a window-wide ring evicts tokens "
                        "the chunk's own queries still attend to")

    # -- queue API ------------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new_tokens: int = 16,
               temperature: float = 0.0, priority: int = 0,
               deadline_s: Optional[float] = None) -> int:
        """Queue a request. ``priority`` is its SLO class (higher = more
        latency-critical: admitted first, given chunk budget first, and
        never preempted by a lower class); ``deadline_s`` orders within a
        class (earliest deadline first, relative to submit time). Both
        default to the old FIFO behavior.

        With an ``admission_policy`` set ("reject" | "downgrade"), a
        deadline-carrying submit is feasibility-checked against the
        measured per-class service rate and the work ranked ahead of it
        (see ``Scheduler.deadline_feasible``): an infeasible deadline is
        either terminally rejected here (status "rejected", reason
        ``deadline_infeasible`` — still returned from ``run``) or
        downgraded to best-effort (deadline stripped, ``downgraded``
        flagged) rather than admitted to miss."""
        r = self.make_request(prompt, max_new_tokens, temperature,
                              priority=priority, deadline_s=deadline_s)
        self.enqueue(r)
        return r.request_id

    def make_request(self, prompt: np.ndarray, max_new_tokens: int = 16,
                     temperature: float = 0.0, priority: int = 0,
                     deadline_s: Optional[float] = None) -> Request:
        """Validate and stamp a request *without* queueing it. The async
        gateway uses this seam to stamp ``submit_s`` at the service
        boundary — time a request spends in the gateway's bounded submit
        queue then counts toward its latency/TTFT/deadline, which the old
        submit-at-grant path silently dropped. ``submit()`` is exactly
        ``make_request`` + ``enqueue``."""
        prompt = validate_prompt(prompt, max_new_tokens, self.max_seq_len,
                                 self.truncate_prompts)
        rid = self._next_id
        self._next_id += 1
        r = Request(rid, prompt, max_new_tokens, temperature,
                    priority=priority, deadline_s=deadline_s)
        r.submit_s = time.perf_counter()
        return r

    def enqueue(self, r: Request, *, ahead_extra: int = 0) -> None:
        """Admission-control gate + engine-queue insert for a made request.
        ``ahead_extra`` counts work queued *upstream* of the engine (the
        gateway's bounded submit queue) so deadline feasibility prices the
        whole line, not just the engine-visible tail; the deadline budget
        is likewise shrunk by the time already spent since ``submit_s``."""
        policy = self.scheduler.admission_policy
        if policy is not None and r.deadline_s is not None:
            mine = request_rank(r)
            ahead = (len(self._slots) + len(self._prefilling) + ahead_extra
                     + sum(1 for q in self._queue if request_rank(q) <= mine))
            remaining = r.deadline_s - (time.perf_counter() - r.submit_s)
            if not self.scheduler.deadline_feasible(
                    deadline_s=remaining, ahead=ahead,
                    priority=r.priority):
                if policy == "reject":
                    self._terminal(
                        r, "rejected",
                        f"deadline_infeasible: {ahead} requests ahead at "
                        f"the measured class service rate cannot finish "
                        f"within {remaining:.3f}s")
                    return
                r.deadline_s = None          # downgrade: serve best-effort
                r.downgraded = True
        self._queue.append(r)

    def queue_depth(self) -> int:
        """Requests waiting in the engine's own queue (resumes included)."""
        return len(self._queue)

    def warm_compile(self) -> None:
        """Pre-compile every admission program (chunk variants, or one
        monolithic prefill per prompt bucket) and every decode-scan
        horizon. Chunk programs retrace per (chunk bucket × context bucket)
        and the scan per horizon in the scheduler's ``k_schedule`` — small
        static sets — and an XLA compile landing mid-traffic (~1 s) would
        dominate some request's TTFT (or a multi-K-token stall), or trip
        the gateway's step watchdog. Each admission variant runs once
        against slot 0 with ``max_new = 0`` and no table row installed;
        each scan variant runs once with every slot inactive
        — so nothing observable changes (masked appends land out of bounds
        or in the trash block, outputs and positions stay untouched, and
        the junk ``last`` logits are re-armed by any real admission). Call
        while idle — before serving traffic — never mid-run.

        Wall time lands in ``warm_compile_s`` (and ``metrics()``): with the
        persistent executable cache armed (``enable_compile_cache``) a
        restarted process replays every compile from disk, so cold-vs-warm
        wall time is the observable the compile cache is judged by."""
        t0 = time.perf_counter()
        if self.scheduler.chunked:
            for bucket in self.scheduler.buckets:
                ctxs = set()
                ctx = _next_pow2(bucket)
                while ctx < self.max_seq_len:
                    ctxs.add(ctx)
                    ctx *= 2
                ctxs.add(self.max_seq_len)
                for ctx in sorted(ctxs):
                    self._cache_state, self._state = self._chunk_fn(
                        self.params, self._cache_state, self._state,
                        jnp.zeros((1, bucket), jnp.int32), jnp.int32(0),
                        jnp.int32(1), jnp.int32(0), jnp.int32(1),
                        jnp.int32(0), jnp.float32(0.0), jnp.int32(0),
                        jnp.bool_(False), ctx)
        else:
            # monolithic admission, one prefill program per prompt bucket:
            # each runs into idle slot 0 with max_new = 0 (left inactive)
            # through an all-(-1) table row, so the paged install lands in
            # the trash block and the ring line is rewritten whole by the
            # slot's next admission
            row = jnp.full((getattr(self.backend, "blocks_per_slot", 1),),
                           -1, jnp.int32)
            for bucket in self.buckets:
                self._cache_state, self._state = self._admit_fn(
                    self.params, self._cache_state, self._state,
                    jnp.zeros((1, bucket), jnp.int32), jnp.int32(1),
                    jnp.int32(0), jnp.int32(0), jnp.float32(0.0),
                    jnp.int32(0), row)
        if hasattr(self, "_copy_fn"):
            # copying the trash block onto itself is a no-op by definition
            self._cache_state = self._copy_fn(self._cache_state,
                                              jnp.int32(0), jnp.int32(0))
        if hasattr(self, "_begin_many_fn"):
            # all-(-1) rows with covered 0 wipe nothing, and an idle
            # engine's slot-0 table row is already -1: a pure no-op
            b, m = self.batch_slots, self.backend.blocks_per_slot
            self._cache_state = self._begin_many_fn(
                self._cache_state, jnp.zeros((b,), jnp.int32),
                jnp.full((b, m), -1, jnp.int32), jnp.zeros((b,), jnp.int32))
        if self._preempt_swap and hasattr(self.backend, "warm_swap"):
            # a first preemption mid-traffic must not pay the swap
            # gather/scatter compiles
            self._cache_state = self.backend.warm_swap(self._cache_state)
        # decode executables: the single step plus every scan horizon the
        # scheduler may pick, so first-request latency never pays scan
        # compilation (all slots inactive -> the run is a pure no-op)
        self._cache_state, self._state = self._step_fn(
            self.params, self._cache_state, self._state, self._base_key)
        for k in self.scheduler.k_schedule:
            if k > 1:
                self._cache_state, self._state = self._scan_fn(
                    self.params, self._cache_state, self._state,
                    self._base_key, k)
        if self.speculative:
            # speculative executables: the draft-fill prefill per prompt
            # bucket (junk K/V written into idle slot 0 sits behind the
            # same pad/overwrite argument as target prefill pads) and the
            # fused propose/verify program per draft depth (all slots
            # inactive -> masked appends, untouched outputs: a pure no-op)
            for bucket in self.buckets:
                self._draft_state = self._draft_fill_fn(
                    self.draft_params, self._draft_state,
                    jnp.zeros((1, bucket), jnp.int32), jnp.int32(0),
                    jnp.int32(0))
            for k in self.scheduler.spec_schedule:
                (self._cache_state, self._draft_state,
                 self._state) = self._spec_fn(
                    self.params, self.draft_params, self._cache_state,
                    self._draft_state, self._state, self._base_key, k)
        jax.block_until_ready(self._state["active"])
        self.warm_compile_s = time.perf_counter() - t0

    @property
    def pending(self) -> bool:
        """Work outstanding: queued, prefilling, or decoding requests."""
        return bool(self._queue or self._slots or self._prefilling)

    def step(self) -> None:
        """Execute one scheduler plan: admissions and prompt chunks first,
        then the decode round. Public so drivers can interleave arrivals
        with serving (see ``benchmarks/bench_serving.py``); ``run`` is just
        this in a drain loop. Timed as the ``engine.step`` span, whose
        record carries the KV gauges (``kv_gauges``)."""
        with tracing.span(tracing.STEP) as sp:
            self._step()
            sp.gauges = self.kv_gauges()

    def _step(self) -> None:
        self._step_count += 1
        slots, free, prefilling = self._slots, self._free, self._prefilling
        if self._faults is not None and self._faults.fire("cancel"):
            # chaos cancellation: a deterministic in-flight victim hangs up
            live = sorted([r.request_id for r in self._queue]
                          + [pp.request.request_id
                             for pp in prefilling.values()]
                          + [r.request_id for r in slots.values()])
            if live:
                self.cancel(self._faults.pick("cancel", live))
        min_headroom = min(
            (r.max_new_tokens - self._scanned.get(s, 0)
             for s, r in slots.items()), default=None)
        with tracing.span("engine.plan"):
            plan = self.scheduler.plan_step(
                n_active=len(slots), prefilling=prefilling,
                try_admit=lambda: self._try_admit(slots, free, prefilling),
                min_headroom=min_headroom,
                try_preempt=lambda: self._try_preempt(slots))
        for c in plan.chunks:
            with tracing.span("engine.chunk",
                              prefilling[c.slot].request.request_id):
                self._run_chunk(c, prefilling, slots)
        if self._pending_swaps:
            # rollback-path swap-outs started their D2H copies
            # asynchronously; the planning/chunk work above overlapped
            # them — materialize before anything can consume a checkpoint
            for h in self._pending_swaps:
                h.resolve()
            self._pending_swaps.clear()
        # occupancy peak counts prefill-only steps too: a step where every
        # live request is still prefilling used to be invisible here
        if slots or prefilling:
            self.peak_active_slots = max(self.peak_active_slots,
                                         len(slots) + len(prefilling))
        if slots:
            try:
                if plan.spec_tokens > 0 and self.speculative:
                    try:
                        self._spec_round(slots, free, self._done,
                                         plan.spec_tokens)
                    except FaultError as e:
                        if e.seam != "draft":
                            raise
                        # the draft dispatch is down: serve this round
                        # without speculation. Commits are target samples
                        # under the baseline key schedule either way, so
                        # the token streams are unchanged — degraded
                        # throughput, never degraded output
                        self.spec_fallbacks += 1
                        self._decode_round(slots, free, self._done,
                                           plan.decode_steps)
                else:
                    self._decode_round(slots, free, self._done,
                                       plan.decode_steps)
            except FaultError as e:
                # the decode dispatch was poisoned *before* touching device
                # state (launch failure semantics), so every active slot
                # still holds its pre-round state: roll them all back to a
                # host checkpoint and requeue with backoff
                self._recover_decode_fault(e.seam)
        # a request too big for the whole pool is terminally rejected in
        # _try_admit; a step where everything waiting is merely backing off
        # (or transiently starved by an injected pool fault) just advances
        # the step counter toward backoff expiry — never a wedge, never an
        # engine-aborting raise

    def run(self) -> Dict[int, Request]:
        """Serve until the queue and all slots drain; returns every request
        completed since the last ``run`` (``step`` completions included)."""
        while self.pending:
            self.step()
        return self.take_done()

    def take_done(self) -> Dict[int, Request]:
        """Drain the terminal-request buffer accumulated since the last
        call (every status: done/failed/rejected/cancelled). The gateway
        polls this after each ``step()`` to resolve handles and close
        streams; ``run()`` is a drain loop ending in one ``take_done``."""
        done, self._done = self._done, {}
        return done

    # -- device-side programs -------------------------------------------------
    def _admit_impl(self, params, cache_state, state, tokens, length, slot,
                    max_new, temp, rid, table_row):
        """Prefill one bucketed prompt and install it into ``slot``.
        True lengths are threaded only for windowed models, where the
        window-wide cache would otherwise keep the padded bucket's trailing
        window and evict live tokens; unwindowed installs keep the cheaper
        contiguous write (pad entries are overwritten before visibility)."""
        logits, one_caches = self.lm.prefill(
            params, {"tokens": tokens}, cache_width=self.max_seq_len,
            lengths=jnp.reshape(length, (1,)) if self._windowed else None,
            mesh=self.mesh, rules=self.rules)
        last = jax.lax.dynamic_index_in_dim(logits[0], length - 1, axis=0,
                                            keepdims=False)
        cache_state = self.backend.prefill_fill(cache_state, one_caches,
                                                slot, length, table_row)
        state = dict(state)
        state["last"] = state["last"].at[slot].set(last.astype(jnp.float32))
        state["pos"] = state["pos"].at[slot].set(length)
        state["steps"] = state["steps"].at[slot].set(0)
        state["budget"] = state["budget"].at[slot].set(max_new)
        state["temp"] = state["temp"].at[slot].set(temp)
        state["rid"] = state["rid"].at[slot].set(rid)
        state["active"] = state["active"].at[slot].set(max_new > 0)
        return cache_state, state

    def _chunk_impl(self, params, cache_state, state, tokens, start, length,
                    slot, prompt_len, max_new, temp, rid, final, ctx):
        """Run one prompt chunk for ``slot`` (scheduler-planned): install
        the chunk's K/V through the slot's cache view and, on the final
        chunk, arm the slot for decode with the last real token's logits.
        ``ctx`` (static) truncates the visible cache to the live prefix —
        the chunk attends to nothing at or above its own padded end."""
        view, tables = self.backend.slot_view(cache_state, slot, ctx)
        t = tokens.shape[1]
        valid = (jnp.arange(t, dtype=jnp.int32) < length)[None, :]
        logits, view = self.lm.prefill_chunk(
            params, view, tokens, jnp.reshape(start, (1,)),
            layout=self.backend.layout, block_tables=tables, valid=valid,
            logits_index=jnp.reshape(length - 1, (1,)),
            mesh=self.mesh, rules=self.rules)
        cache_state = self.backend.slot_update(cache_state, slot, view)
        last = logits[0, 0]
        state = dict(state)
        state["last"] = state["last"].at[slot].set(
            jnp.where(final, last.astype(jnp.float32), state["last"][slot]))
        state["pos"] = state["pos"].at[slot].set(prompt_len)
        state["steps"] = state["steps"].at[slot].set(0)
        state["budget"] = state["budget"].at[slot].set(max_new)
        state["temp"] = state["temp"].at[slot].set(temp)
        state["rid"] = state["rid"].at[slot].set(rid)
        state["active"] = state["active"].at[slot].set(final & (max_new > 0))
        return cache_state, state

    def _step_impl(self, params, cache_state, state, base_key):
        """Fused decode step: sample → append → done-detect, on device.
        Sampling keys fold (request_id, step) into ``base_key``, so a
        request's stream is independent of its co-scheduled neighbors."""
        active = state["active"]
        keys = request_keys(base_key, state["rid"], state["steps"])
        nxt = sample_logits_keyed(keys, state["last"], state["temp"])
        rows = jnp.arange(self.batch_slots)
        idx = jnp.clip(state["steps"], 0, self.max_seq_len - 1)
        out = state["out"].at[rows, idx].set(
            jnp.where(active, nxt, state["out"][rows, idx]))
        steps = state["steps"] + active.astype(jnp.int32)
        feed = jnp.where(active, nxt, 0)[:, None]
        # inactive rows (free slots, mid-prefill slots) must not write their
        # junk token into the cache: valid-masked append drops them
        logits, caches = self.lm.decode_step(
            params, cache_state["caches"], feed, state["pos"],
            layout=self.backend.layout,
            block_tables=cache_state["tables"],
            valid=active[:, None], mesh=self.mesh, rules=self.rules)
        finished = steps >= state["budget"]
        if self.eos_id is not None:
            finished |= nxt == self.eos_id
        state = {
            "last": logits[:, 0, :].astype(jnp.float32),
            "pos": state["pos"] + active.astype(jnp.int32),
            "steps": steps,
            "budget": state["budget"],
            "temp": state["temp"],
            "rid": state["rid"],
            "active": active & ~finished,
            "out": out,
        }
        return {"caches": caches, "tables": cache_state["tables"]}, state

    def _scan_impl(self, params, cache_state, state, base_key, k):
        """Multi-step decode: ``lax.scan`` ``k`` (static) fused decode
        steps inside one jit — one dispatch and one host sync per ``k``
        tokens. The carry is exactly the single-step program's
        (caches, state): sampling keys fold the *carried* (request_id,
        steps), positions and the active mask advance on device, and rows
        that finish mid-scan (EOS / budget) go inactive and no-op through
        the remaining iterations (masked appends, unwritten outputs) — so
        outputs are token-for-token the K=1 engine's at every k. Block
        tables are scan-invariant (the host reserves look-ahead blocks
        before dispatch), so they ride as a closure constant, not carry."""
        tables = cache_state["tables"]

        def body(carry, _):
            caches, st = carry
            new_cache, st = self._step_impl(
                params, {"caches": caches, "tables": tables}, st, base_key)
            return (new_cache["caches"], st), None

        (caches, state), _ = jax.lax.scan(
            body, (cache_state["caches"], state), xs=None, length=k)
        return {"caches": caches, "tables": tables}, state

    def _draft_fill_impl(self, draft_params, draft_state, tokens, length,
                         slot):
        """Install one bucketed token stream into the draft ring — the
        draft-side analogue of ``_admit_impl`` minus sampling state (the
        speculative program derives everything it needs from the target's
        carry). ``prefill_fill`` replaces the whole slot row, so pad
        entries and any previous tenant's K/V vanish together."""
        _, one_caches = self.draft_lm.prefill(
            draft_params, {"tokens": tokens}, cache_width=self.max_seq_len,
            last_only=True,
            lengths=jnp.reshape(length, (1,)) if self._draft_windowed
            else None, mesh=self.mesh, rules=self.rules)
        return self._draft_backend.prefill_fill(draft_state, one_caches,
                                                slot, length, None)

    def _spec_impl(self, params, draft_params, cache_state, draft_state,
                   state, base_key, k):
        """One fused propose-k/verify round: draft scan → one chunked
        target dispatch → accept → commit, all on device.

        Verification is **key-coupled**: the anchor token ``t0`` is
        sampled from the carried ``last`` logits with exactly the key the
        plain step would fold, the draft proposes ``d_1..d_k`` with the
        keys of the *following* steps, the target attends the whole
        (k+1)-token chunk ``[t0, d_1..d_k]`` in one ``prefill_chunk``
        call, and ``s_i`` — sampled from the target's verify logits with
        the same folded key as ``d_i`` — is precisely the token the
        baseline engine would emit at that step. A proposal is accepted
        iff it *equals* its baseline token, so every committed token is a
        baseline token: speculative streams are token-for-token identical
        to K=1 at every temperature (greedy included — argmax is the
        temperature-0 case of the same coupling). On a rejection the
        corrected token is not committed here; it re-emerges as the next
        round's anchor — same key, same logits, same token.

        The draft scan runs k+1 iterations: the last consumes ``d_k`` so
        the draft cache stays contiguous through a fully-accepted round
        (its sampled output is discarded). Both caches mask appends to
        ``i < headroom`` — a token at or past the budget edge can never
        commit, and the mask keeps every append inside the slot's
        reservation (ring width / paged look-ahead)."""
        b = self.batch_slots
        active = state["active"]
        rid, steps, temp, pos = (state["rid"], state["steps"],
                                 state["temp"], state["pos"])
        headroom = state["budget"] - steps       # >= 1 on active rows
        t0 = sample_logits_keyed(
            request_keys(base_key, rid, steps), state["last"], temp)

        def draft_body(carry, i):
            dcaches, tok = carry
            ok = active & (i < headroom)
            feed = jnp.where(active, tok, 0)[:, None]
            dlogits, dcaches = self.draft_lm.decode_step(
                draft_params, dcaches, feed, pos + i,
                layout=self._draft_backend.layout, block_tables=None,
                valid=ok[:, None], mesh=self.mesh, rules=self.rules)
            nxt = sample_logits_keyed(
                request_keys(base_key, rid, steps + i + 1),
                dlogits[:, 0, :].astype(jnp.float32), temp)
            return (dcaches, nxt), nxt

        (dcaches, _), drafted = jax.lax.scan(
            draft_body, (draft_state["caches"], t0),
            jnp.arange(k + 1, dtype=jnp.int32))
        proposals = jnp.moveaxis(drafted, 0, 1)[:, :k]          # (B, k)

        chunk = jnp.concatenate([t0[:, None], proposals], axis=1)
        offs = jnp.arange(k + 1, dtype=jnp.int32)
        ok = active[:, None] & (offs[None, :] < headroom[:, None])
        logits, caches = self.lm.prefill_chunk(
            params, cache_state["caches"], chunk, pos,
            layout=self.backend.layout, block_tables=cache_state["tables"],
            valid=ok, mesh=self.mesh, rules=self.rules)
        logits = logits.astype(jnp.float32)                 # (B, k+1, V)

        # s_i reads logits row i-1: the target's distribution after the
        # first i chunk tokens, i.e. the baseline ``last`` at step steps+i.
        # All k verifications fold keys and sample as one flattened batch:
        # per-element results are identical to k separate calls, but the
        # program carries one fold/categorical op pair instead of k — on
        # a small-model host the op count, not the FLOPs, is the cost
        ksteps = (steps[:, None] + offs[None, 1:]).reshape(-1)   # (B*k,)
        krid = jnp.broadcast_to(rid[:, None], (b, k)).reshape(-1)
        ktemp = jnp.broadcast_to(temp[:, None], (b, k)).reshape(-1)
        target_toks = sample_logits_keyed(
            request_keys(base_key, krid, ksteps),
            logits[:, :k, :].reshape(b * k, logits.shape[-1]),
            ktemp).reshape(b, k)                             # (B, k)
        j = accepted_prefix_length(proposals, target_toks)  # (B,) in [0,k]
        commit = jnp.minimum(1 + j, headroom)
        eos_hit = jnp.zeros((b,), jnp.bool_)
        if self.eos_id is not None:
            is_eos = chunk == self.eos_id
            has_eos = jnp.any(is_eos, axis=1)
            eos_idx = jnp.argmax(is_eos, axis=1)    # first EOS in the chunk
            commit = jnp.where(has_eos,
                               jnp.minimum(commit, eos_idx + 1), commit)
            eos_hit = has_eos & (eos_idx < commit)

        rows = jnp.arange(b)
        write = ok & (offs[None, :] < commit[:, None])
        idx = jnp.clip(steps[:, None] + offs[None, :], 0,
                       self.max_seq_len - 1)
        out = state["out"].at[rows[:, None], idx].set(
            jnp.where(write, chunk, state["out"][rows[:, None], idx]))
        # logits row commit-1 is the distribution for the step after the
        # last committed token — exactly the ``last`` the baseline carry
        # would hold there
        last = jnp.take_along_axis(
            logits, jnp.clip(commit - 1, 0, k)[:, None, None], axis=1)[:, 0]
        last = jnp.where(active[:, None], last, state["last"])
        dcommit = jnp.where(active, commit, 0)
        new_steps = steps + dcommit
        finished = (new_steps >= state["budget"]) | eos_hit
        state = {
            "last": last,
            "pos": pos + dcommit,
            "steps": new_steps,
            "budget": state["budget"],
            "temp": temp,
            "rid": rid,
            "active": active & ~finished,
            "out": out,
        }
        return ({"caches": caches, "tables": cache_state["tables"]},
                {"caches": dcaches, "tables": draft_state["tables"]},
                state)

    # -- host-side management -------------------------------------------------
    def _try_admit(self, slots, free, prefilling):
        """Scheduler admission callback: grant the *best-ranked* waiting
        request (class, then deadline, then submission order) a slot plus
        its cache reservation, or return None. Ordering is strict — a
        lower-class request never backfills in front of a blocked
        higher-class one, because its blocks could stall the critical
        request for a whole generation. Chunked admissions return a
        ``PrefillProgress`` (the scheduler plans their chunks); legacy,
        swap-resumed and recompute-resumed-monolithic admissions return
        MONOLITHIC (nothing left to chunk).

        Requests under fault backoff (``not_before_step``) are skipped
        until their backoff expires — a retrying request must not block
        the queue during its own cool-down. Requests that could never fit
        even in an idle pool (``can_ever_admit``) are terminally rejected
        here with a machine-readable reason rather than raising: one bad
        submit never aborts ``run()`` for everyone else."""
        if not free:
            return None
        while True:
            eligible = [q for q in self._queue
                        if q.not_before_step <= self._step_count]
            if not eligible:
                return None
            r = min(eligible, key=request_rank)
            if not self.backend.can_ever_admit(len(r.prompt),
                                               r.max_new_tokens):
                self._queue.remove(r)
                self._terminal(
                    r, "rejected",
                    f"exceeds_pool_capacity: prompt {len(r.prompt)} + "
                    f"budget {r.max_new_tokens} needs more KV blocks than "
                    f"the whole pool holds; enlarge num_pool_blocks")
                continue
            break
        if self._faults is not None and self._faults.fire("pool"):
            # transient block-pool exhaustion: admission simply answers
            # "no blocks" this step and retries on the next one
            return None
        if r.resume is not None and r.resume.kv is not None:
            # swap path: restore the checkpointed blocks, no prefill at all
            if not self.backend.can_resume(len(r.prompt), r.max_new_tokens):
                return None
            if self._faults is not None and self._faults.fire("swap_in"):
                # the K/V checkpoint failed to restore (fires before the
                # backend draws blocks, so nothing to unwind): drop it and
                # fall back to the recompute-resume path — the host
                # checkpoint (tokens + last logits) rebuilds the cache
                # exactly, so the stream stays token-for-token identical
                r.resume.kv = None
                self._record_retry(r, "swap_in")
                return None
            with tracing.span("engine.admit", r.request_id):
                self._queue.remove(r)
                slot = free.pop()
                self._cache_state = self.backend.swap_in(
                    self._cache_state, slot, r.resume.kv, len(r.prompt),
                    r.max_new_tokens)
                self._note_grant(r)
                self._arm_resumed(r, slot, slots)
            return MONOLITHIC
        # fresh admission, or recompute-resume (re-prefill prompt + already
        # generated tokens; the decode checkpoint is restored at arming)
        tokens = r.prompt if r.resume is None else np.concatenate(
            [r.prompt, r.resume.tokens]).astype(np.int32)
        remaining = r.max_new_tokens - (r.resume.steps if r.resume else 0)
        key = tokens if (self._admit_with_tokens and r.resume is None) \
            else len(tokens)
        if not self.backend.can_admit(key, remaining):
            return None
        with tracing.span("engine.admit", r.request_id):
            return self._grant(r, tokens, key, remaining, slots, free,
                               prefilling)

    def _grant(self, r: Request, tokens: np.ndarray, key, remaining: int,
               slots, free, prefilling):
        """Admit ``r`` (fresh, or resumed by recompute) into a free slot:
        a monolithic prefill, or the slot's blocks and table row ahead of
        its prompt chunks."""
        self._queue.remove(r)
        slot = free.pop()
        if not self.scheduler.chunked:
            self._admit(r, slot, slots, tokens, remaining)
            return MONOLITHIC
        table_row = self.backend.alloc_slot(slot, key, remaining)
        start = self.backend.shared_prefill_start(slot)
        shared_blocks = self.backend.shared_block_count(slot)
        for src, dst in self.backend.take_pending_copies():
            self._cache_state = self._copy_fn(
                self._cache_state, jnp.int32(src), jnp.int32(dst))
        self._cache_state = self._begin_fn(
            self._cache_state, jnp.int32(slot), jnp.asarray(table_row),
            jnp.int32(shared_blocks))
        self._note_grant(r)
        self.prefill_tokens_total += len(tokens)
        self.prefill_tokens_skipped += start
        pp = PrefillProgress(request=r, slot=slot, next=start,
                             total=len(tokens),
                             tokens=tokens if r.resume is not None else None)
        prefilling[slot] = pp
        return pp

    def _run_chunk(self, c, prefilling, slots):
        pp = prefilling[c.slot]
        r = pp.request
        src = pp.tokens if pp.tokens is not None else r.prompt
        self.planned_token_slots += c.bucket
        self.useful_prefill_tokens += c.length
        tokens = np.zeros((1, c.bucket), np.int32)
        tokens[0, :c.length] = src[c.start:c.start + c.length]
        # static context bound: next power of two covering the padded chunk
        # end (bounded retrace set: |chunk buckets| x |context buckets|)
        ctx = min(self.max_seq_len, _next_pow2(c.start + c.bucket))
        self._cache_state, self._state = self._chunk_fn(
            self.params, self._cache_state, self._state, jnp.asarray(tokens),
            jnp.int32(c.start), jnp.int32(c.length), jnp.int32(c.slot),
            jnp.int32(len(src)), jnp.int32(r.max_new_tokens),
            jnp.float32(r.temperature), jnp.int32(r.request_id),
            jnp.bool_(c.final), ctx)
        pp.next = c.start + c.length
        if c.final:
            del prefilling[c.slot]
            if self.speculative:
                # arm the draft cache with the slot's whole visible stream
                # (prompt, or prompt + generated on a recompute-resume)
                self._draft_fill(c.slot, np.asarray(src, np.int32))
            if r.resume is None:
                # the slot's full prompt blocks now hold real K/V: publish
                # them for prefix sharing by later admissions (a resumed
                # request's token stream includes generated tokens — never
                # published as a "prompt")
                self.backend.register_prefix(c.slot, r.prompt)
                self._scanned[c.slot] = 0
            else:
                self._restore_checkpoint(r, c.slot)
            slots[c.slot] = r

    def _admit(self, r: Request, slot: int, slots: Dict[int, Request],
               tokens_1d: np.ndarray, remaining: int):
        """Monolithic (unchunked) admission: prefill ``tokens_1d`` — the
        prompt, or prompt + generated for a recompute-resume — into the
        slot and arm it for decode. ``remaining`` sizes the cache
        reservation (decode tokens still to come)."""
        length = len(tokens_1d)
        bucket = bucket_for(length, self.buckets)
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :length] = tokens_1d                   # right-pad (exact)
        table_row = self.backend.alloc_slot(slot, length, remaining)
        self._cache_state, self._state = self._admit_fn(
            self.params, self._cache_state, self._state, jnp.asarray(tokens),
            jnp.int32(length), jnp.int32(slot), jnp.int32(r.max_new_tokens),
            jnp.float32(r.temperature), jnp.int32(r.request_id),
            jnp.asarray(table_row))
        self._note_grant(r)
        self.prefill_tokens_total += length
        self.planned_token_slots += bucket
        self.useful_prefill_tokens += length
        if self.speculative:
            self._draft_fill(slot, tokens_1d)
        if r.resume is None:
            self._scanned[slot] = 0
        else:
            self._restore_checkpoint(r, slot)
        slots[slot] = r

    def _edit_state(self, **rows) -> None:
        """Host-side single-slot state edit: whole-array device↔host
        round-trips instead of eager sliced updates. A sliced jnp edit
        (``x.at[slot, :steps].set``) compiles a fresh executable per
        (slot, steps) shape — a preemption would stall ~100 ms on XLA
        every time it saw a new checkpoint size. Plain transfers never
        compile, and the state arrays are a few KB."""
        st = dict(self._state)
        for key, (slot, value) in rows.items():
            arr = np.array(st[key])          # device→host copy, no compile
            arr[slot] = value
            st[key] = jnp.asarray(arr)       # host→device, no compile
        self._state = st

    def _restore_checkpoint(self, r: Request, slot: int) -> None:
        """Re-arm a resumed slot's decode state from the preemption
        checkpoint: step counter, generated-token buffer and — crucially —
        the saved ``last`` logits, so the next sampled token is bit-exact
        regardless of how the K/V came back (swap or recompute). Sampling
        keys fold (request_id, steps), so the stream continues exactly
        where it stopped."""
        rs = r.resume
        out = np.zeros((self.max_seq_len,), np.int32)
        out[:rs.steps] = rs.tokens
        self._edit_state(steps=(slot, rs.steps), last=(slot, rs.last),
                         out=(slot, out))
        self._scanned[slot] = rs.steps
        r.resume = None

    def _arm_resumed(self, r: Request, slot: int, slots) -> None:
        """Swap-path resume: the K/V blocks are already restored, so the
        whole slot state (position, budget, temperature, active) is armed
        host-side — no prefill runs at all."""
        rs = r.resume
        self._edit_state(pos=(slot, len(r.prompt) + rs.steps),
                         budget=(slot, r.max_new_tokens),
                         temp=(slot, r.temperature),
                         rid=(slot, r.request_id),
                         # a budget-0 slot is reaped, never decoded — the
                         # same admission-time rule the prefill paths apply
                         active=(slot, rs.steps < r.max_new_tokens))
        if self.speculative:
            # the swap checkpoint restores only the target's K/V; the
            # draft cache is rebuilt from the host token stream
            self._draft_fill(slot, np.concatenate(
                [r.prompt, rs.tokens]).astype(np.int32))
        self._restore_checkpoint(r, slot)
        slots[slot] = r

    def _rollback_slot(self, slot: int) -> Request:
        """Evict ``slot`` back to a host checkpoint — the shared primitive
        under SLO preemption *and* fault recovery. Decode state (generated
        tokens, step count, next-sample logits) is checkpointed on the
        host; the cache either rides along (``PagedCache.swap_out`` —
        blocks return to the pool) or is rebuilt at resume by
        re-prefilling prompt + generated tokens (ring /
        ``preempt_mode='recompute'``). A ``swap_out`` seam fault degrades
        to the recompute path — strictly slower, never less exact. The
        caller decides what the eviction *means* (preemption vs retry)
        and where the request goes next."""
        r = self._slots.pop(slot)
        st = self._state
        steps = int(np.asarray(st["steps"])[slot])   # transfer, no compile
        r.resume = _ResumeState(
            steps=steps,
            tokens=np.array(np.asarray(st["out"])[slot, :steps]),
            last=np.array(np.asarray(st["last"])[slot]))
        self._edit_state(active=(slot, False))
        swap = self._preempt_swap
        if swap and self._faults is not None \
                and self._faults.fire("swap_out"):
            r.last_fault = "swap_out"    # checkpoint transport failed:
            swap = False                 # recompute resume instead (exact)
        if swap:
            # deferred D2H: the gather lands in a fresh device buffer, the
            # host copy streams in the background and is resolved after
            # the next scheduler plan (see step()) — the rollback path no
            # longer stalls the step loop on the transfer
            r.resume.kv, self._cache_state = self.backend.swap_out(
                self._cache_state, slot, defer=True)
            self._pending_swaps.append(r.resume.kv["caches"])
        else:
            self._cache_state = self.backend.free_slot(self._cache_state,
                                                       slot)
        self._scanned.pop(slot, None)
        if self.speculative:
            self._draft_dirty.discard(slot)
        self._free.append(slot)
        return r

    def preempt(self, slot: int) -> None:
        """Swap the request decoding in ``slot`` out and requeue it (see
        ``_rollback_slot``). Resumption is token-exact. Called by the
        scheduler under SLO pressure; public so drivers and tests can
        force arbitrary preemption schedules."""
        r = self._rollback_slot(slot)
        r.preemptions += 1
        self.preemptions += 1
        self._queue.append(r)

    # -- fault tolerance ------------------------------------------------------
    def _recover_decode_fault(self, seam: str) -> None:
        """A decode dispatch was poisoned before mutating device state:
        roll every active slot back to a host checkpoint and requeue with
        bounded exponential backoff; requests exceeding the retry budget
        are quarantined (terminal "failed") instead of wedging the loop."""
        self.fault_recoveries += 1
        for slot in list(self._slots):
            r = self._rollback_slot(slot)
            self._record_retry(r, seam, in_queue=False)

    def _record_retry(self, r: Request, seam: str,
                      in_queue: bool = True) -> None:
        """Account one fault-triggered retry for ``r`` and route it:
        backoff + requeue within budget, quarantine beyond it.
        ``in_queue`` says whether ``r`` currently sits in the queue (a
        swap-in fault) or was just rolled out of a slot."""
        r.retries += 1
        r.last_fault = seam
        r.fault_s = time.perf_counter()
        self.retries_total += 1
        if r.retries > self.max_retries:
            if in_queue:
                self._queue.remove(r)
            self._quarantine(r, seam)
            return
        r.not_before_step = self._step_count + min(
            self.backoff_cap_steps,
            self.backoff_base_steps << (r.retries - 1))
        if not in_queue:
            self._queue.append(r)

    def _quarantine(self, r: Request, seam: str) -> None:
        """Terminal failure: the request exhausted its retry budget. Its
        partial output (tokens generated before the last fault) is kept;
        its checkpoint (and any host K/V) is dropped."""
        out = (r.resume.tokens if r.resume is not None
               else np.zeros((0,), np.int32))
        r.resume = None
        self._terminal(
            r, "failed",
            f"retry_budget_exhausted: {r.retries} retries > "
            f"max_retries={self.max_retries} (last fault: {seam})",
            output=out)

    def _terminal(self, r: Request, status: str, reason: Optional[str],
                  output: Optional[np.ndarray] = None) -> None:
        """Move ``r`` to a terminal disposition and into ``_done`` (the
        caller has already detached it from queue/slots/prefilling).
        ``output`` defaults to empty so downstream accounting never trips
        on None."""
        r.status = status
        r.failure_reason = reason
        if r.output is None:
            r.output = output if output is not None \
                else np.zeros((0,), np.int32)
        r.finish_s = time.perf_counter()
        r.latency_s = r.finish_s - r.submit_s
        self._emitted.pop(r.request_id, None)
        if status == "failed" and r.deadline_s is not None:
            # quarantine is a deadline miss: the client asked for a result
            # by a time and will never get one. Cancelled/rejected requests
            # are *not* counted — the client withdrew / was never admitted.
            self.scheduler.observe_deadline(r.priority, False)
        self._status_counts[status] += 1
        self._done[r.request_id] = r

    def _note_grant(self, r: Request) -> None:
        """Slot-grant bookkeeping shared by every admission path: sticky
        first-admission stamp (resume never restamps) and, after a fault
        requeue, the recovery latency (fault -> re-grant)."""
        if r.admit_s == 0.0:
            r.admit_s = time.perf_counter()
        if r.fault_s:
            self.recovery_latencies.append(time.perf_counter() - r.fault_s)
            r.fault_s = 0.0

    def cancel(self, request_id: int) -> bool:
        """Cancel an in-flight request wherever it currently lives —
        queued (preempted included), mid-prefill, or mid-decode. Its
        resources (slot, pool blocks) are released immediately, partial
        output is kept, and it lands in ``run()``'s results with terminal
        status "cancelled". Returns False when the id isn't in flight
        (already finished, or never submitted)."""
        for r in self._queue:
            if r.request_id == request_id:
                self._queue.remove(r)
                out = (r.resume.tokens if r.resume is not None
                       else np.zeros((0,), np.int32))
                r.resume = None
                self._terminal(r, "cancelled", "cancelled: while queued",
                               output=out)
                return True
        for slot, pp in list(self._prefilling.items()):
            if pp.request.request_id == request_id:
                del self._prefilling[slot]
                # the installed chunks are abandoned: blocks return to the
                # pool, stale cache entries are wiped by the next tenant's
                # begin_slot
                self._cache_state = self.backend.free_slot(
                    self._cache_state, slot)
                self._free.append(slot)
                r = pp.request
                r.resume = None
                self._terminal(r, "cancelled", "cancelled: mid-prefill")
                return True
        for slot, r in list(self._slots.items()):
            if r.request_id == request_id:
                self._slots.pop(slot)
                steps = int(np.asarray(self._state["steps"])[slot])
                out = np.array(
                    np.asarray(self._state["out"])[slot, :steps])
                self._edit_state(active=(slot, False))
                self._cache_state = self.backend.free_slot(
                    self._cache_state, slot)
                self._scanned.pop(slot, None)
                if self.speculative:
                    self._draft_dirty.discard(slot)
                self._free.append(slot)
                self._terminal(r, "cancelled", "cancelled: mid-decode",
                               output=out)
                return True
        return False

    def metrics(self) -> Dict[str, object]:
        """Monitoring snapshot: live/terminal request counts, fault and
        recovery accounting, and the core serving counters — the payload
        ``core.monitoring.MonitoringService.record_serving`` ingests."""
        lat = sorted(self.recovery_latencies)

        def pct(p: float) -> float:
            return lat[min(len(lat) - 1, int(p * len(lat)))] if lat else 0.0

        return {
            "live": {"queued": len(self._queue),
                     "prefilling": len(self._prefilling),
                     "decoding": len(self._slots)},
            "terminal": dict(self._status_counts),
            "quarantined": self._status_counts.get("failed", 0),
            "retries_total": self.retries_total,
            "fault_recoveries": self.fault_recoveries,
            "faults_injected": (self._faults.fired()
                                if self._faults is not None else {}),
            "recovery": {"count": len(lat), "p50_s": pct(0.50),
                         "p99_s": pct(0.99)},
            "preemptions": self.preemptions,
            "generated_tokens": self.generated_tokens,
            "host_syncs": self.host_syncs,
            "occupancy": self.occupancy(),
            "deadline_hits": self.scheduler.deadline_hit_rates(),
            "speculative": self.speculative_metrics(),
            "restores": self.restores,
            "hang_recoveries": self.hang_recoveries,
            "warm_compile_s": self.warm_compile_s,
            "mesh_devices": self.mesh.size if self.mesh is not None else 1,
            # {span: {"count", "s"}} over the process (repro.serving.tracing)
            "host_phases": tracing.totals(),
        }

    def speculative_metrics(self) -> Dict[str, object]:
        """Speculation accounting: drafted vs accepted proposals overall
        and per SLO class, plus committed tokens per speculative dispatch
        (1 + per-slot acceptance — the quantity that has to beat a plain
        step's guaranteed 1 for drafting to pay). All-zero, same shape,
        on an engine without a draft model."""
        drafted, accepted = self.spec_drafted_tokens, self.spec_accepted_tokens
        return {
            "enabled": self.speculative,
            "rounds": self.spec_rounds,
            "slot_rounds": self.spec_slot_rounds,
            "fallbacks": self.spec_fallbacks,
            "drafted_tokens": drafted,
            "accepted_tokens": accepted,
            "committed_tokens": self.spec_committed_tokens,
            "acceptance_rate": accepted / drafted if drafted else 0.0,
            "committed_per_dispatch": (
                self.spec_committed_tokens / self.spec_slot_rounds
                if self.spec_slot_rounds else 0.0),
            "per_class": {
                p: {"drafted": d, "accepted": a,
                    "rate": a / d if d else 0.0}
                for p, (d, a) in sorted(self._spec_class.items())},
        }

    def _try_preempt(self, slots) -> bool:
        """Scheduler preemption callback: when the best-ranked waiting
        request is blocked on resources, swap out the worst-ranked active
        slot — strictly lower class only (deadlines order service, they
        never justify eviction; equal-class preemption would thrash).
        Mid-prefill slots are not victims: their checkpoint would be pure
        waste (no decode state yet) and they release the pool soonest."""
        if not self._queue or not slots:
            return False
        blocked = min(self._queue, key=request_rank)
        if hasattr(self.backend, "blocks_needed"):
            # feasibility first: eviction only helps if the blocks it can
            # ever recover — the uncommitted free list plus everything
            # held by strictly-lower-class slots — cover the blocked
            # request's worst case. Without this, an oversized (or merely
            # over-contended) request would swap out the whole lower-class
            # active set one host round-trip at a time for nothing.
            # worst-case demand (a shared-prefix admission may need less;
            # the guard then errs toward letting the high-class request
            # wait rather than toward evicting in vain)
            worst = self.backend.blocks_needed(len(blocked.prompt),
                                               blocked.max_new_tokens)
            recoverable = self.backend.available_blocks() + sum(
                self.backend.slot_commitment(s)
                for s, req in slots.items()
                if req.priority < blocked.priority)
            if worst > recoverable:
                return False
        victim = max(slots, key=lambda s: request_rank(slots[s]))
        if slots[victim].priority >= blocked.priority:
            return False
        self.preempt(victim)
        return True

    def _reserve_lookahead(self, slots, k: int) -> None:
        """Top every active slot's cache reservation up to ``pos + k``
        tokens before a decode round: inside a K-scan the host cannot
        intervene, so each append the scan will perform must already have
        an allocated block. The allocator's admission-time commitment
        guarantees the draw succeeds; the new rows replay through the
        ``begin_slots`` seam — every slot that crossed a block boundary in
        this plan lands in *one* coalesced device update (padded to
        ``batch_slots`` by repetition, so it compiles exactly once)
        instead of one small dispatch per crossing slot."""
        ups = []
        for slot, r in slots.items():
            row, covered = self.backend.reserve_lookahead(
                slot, len(r.prompt) + self._scanned[slot] + k)
            if row is not None:
                ups.append((slot, row, covered))
        if not ups:
            return
        self.lookahead_dispatches += 1
        if not hasattr(self, "_begin_many_fn"):
            for slot, row, covered in ups:       # backend without batching
                self._cache_state = self._begin_fn(
                    self._cache_state, jnp.int32(slot), jnp.asarray(row),
                    jnp.int32(covered))
            return
        while len(ups) < self.batch_slots:       # pad by repeating: the
            ups.append(ups[0])                   # duplicate writes agree
        s, rows, cov = zip(*ups)
        self._cache_state = self._begin_many_fn(
            self._cache_state, jnp.asarray(s, jnp.int32),
            jnp.asarray(np.stack(rows)), jnp.asarray(cov, jnp.int32))

    def _decode_round(self, slots, free, done, k: int = 1):
        if not slots:
            return
        with tracing.span("engine.reserve"):
            self._reserve_lookahead(slots, k)
        if self._faults is not None:
            if self._faults.fire("hang"):
                # a hung dispatch: stall without raising — no exception
                # path ever sees this, only the gateway's wall-clock
                # watchdog around the step (which then escalates through
                # note_hang -> the ordinary rollback/retry ladder)
                time.sleep(self._faults.hang_s)
            # a poisoned dispatch fails at launch, before the donated
            # buffers are touched — device state is intact, which is what
            # lets _recover_decode_fault checkpoint from it (the look-ahead
            # reservation above already landed; rollback returns it through
            # the ordinary free/swap path)
            self._faults.check("scan" if k > 1 else "step",
                               f"decode round over {len(slots)} slots")
        with tracing.span("engine.dispatch"):
            if k == 1:
                self._cache_state, self._state = self._step_fn(
                    self.params, self._cache_state, self._state,
                    self._base_key)
            else:
                self._cache_state, self._state = self._scan_fn(
                    self.params, self._cache_state, self._state,
                    self._base_key, k)
        self.decode_steps += k
        self.host_syncs += 1
        self.planned_token_slots += len(slots) * k
        for slot in slots:
            self._scanned[slot] += k
        if self.speculative:
            # the draft cache saw none of this round's tokens: mark the
            # slots so the next speculative round re-syncs them first
            self._draft_dirty.update(slots.keys())
        with tracing.span("engine.sync"):
            active = np.asarray(self._state["active"])   # the one host sync
        self._finish_round(slots, free, done, active)

    def _spec_round(self, slots, free, done, k: int):
        """One speculative propose-k/verify round (see ``_spec_impl``).
        The look-ahead reservation covers the worst case — the anchor plus
        all k proposals accepted — so the verify append can never fault
        mid-dispatch; rejected tails were masked out of the cache and cost
        only the token-slots ``occupancy`` charges for them."""
        if self._faults is not None:
            # the draft seam fails the whole speculative dispatch at
            # launch, before any state is touched: step() serves the round
            # through the plain decode path instead (exact either way)
            self._faults.check(
                "draft", f"speculative round over {len(slots)} slots, k={k}")
        self._resync_draft(slots)
        with tracing.span("engine.reserve"):
            self._reserve_lookahead(slots, k + 1)
        before = dict(self._scanned)
        with tracing.span("engine.dispatch"):
            (self._cache_state, self._draft_state,
             self._state) = self._spec_fn(
                self.params, self.draft_params, self._cache_state,
                self._draft_state, self._state, self._base_key, k)
        self.host_syncs += 1
        self.planned_token_slots += len(slots) * (k + 1)
        self.spec_rounds += 1
        with tracing.span("engine.sync"):
            active = np.asarray(self._state["active"])   # the one host sync
        with tracing.span("engine.pull"):
            steps_h = np.asarray(self._state["steps"])
        accepted_total = 0
        for slot, r in slots.items():
            committed = int(steps_h[slot]) - before[slot]
            self._scanned[slot] = int(steps_h[slot])
            self.decode_steps += committed
            self.spec_slot_rounds += 1
            self.spec_drafted_tokens += k
            self.spec_committed_tokens += committed
            acc = max(0, committed - 1)   # anchor token is never "accepted"
            self.spec_accepted_tokens += acc
            accepted_total += acc
            d, a = self._spec_class.get(r.priority, (0, 0))
            self._spec_class[r.priority] = (d + k, a + acc)
        self.scheduler.observe_speculation(len(slots), len(slots) * k,
                                           accepted_total)
        self._finish_round(slots, free, done, active, steps_h=steps_h)

    def _resync_draft(self, slots) -> None:
        """Rebuild the draft cache for slots that advanced through plain
        decode rounds (the draft saw none of those tokens): one bucketed
        draft prefill of prompt + generated per dirty slot. Transitions
        are rare — a burst of prefill work collapses speculation for its
        duration, then each affected slot pays this once."""
        dirty = [s for s in slots if s in self._draft_dirty]
        if not dirty:
            return
        steps_h = np.asarray(self._state["steps"])
        out_h = np.asarray(self._state["out"])
        for slot in dirty:
            r = slots[slot]
            n = int(steps_h[slot])
            self._draft_fill(slot, np.concatenate(
                [r.prompt, out_h[slot, :n]]).astype(np.int32))

    def _draft_fill(self, slot: int, tokens_1d: np.ndarray) -> None:
        """Prefill the draft cache for ``slot`` with its full visible
        stream (prompt, plus generated tokens on resume / re-sync),
        bucketed like target prefill so the retrace set stays
        ``|buckets|``."""
        length = len(tokens_1d)
        bucket = bucket_for(length, self.buckets)
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :length] = tokens_1d
        self._draft_state = self._draft_fill_fn(
            self.draft_params, self._draft_state, jnp.asarray(tokens),
            jnp.int32(length), jnp.int32(slot))
        self._draft_dirty.discard(slot)

    def _finish_round(self, slots, free, done, active, steps_h=None):
        """Post-sync bookkeeping shared by plain and speculative decode
        rounds (``active`` is the mask the round's sync pulled): TTFT
        stamps, the stream tap, completion handling. Device reads are
        whole-array pulls sliced host-side — an eager per-completion
        ``state["out"][slot, :n]`` compiles a fresh tiny executable per
        (slot, n) shape, which is exactly the cold-probe capacity cost the
        open-loop bench used to dodge with a throwaway warm pass."""
        now = time.perf_counter()
        finished = [s for s in slots if not active[s]]
        with tracing.span("engine.pull"):
            for r in slots.values():
                # every budget>0 member banked >= 1 token in the round
                # above; budget-0 requests never produce one and get no TTFT
                if r.ttft_s == 0.0 and r.max_new_tokens > 0:
                    r.ttft_s = now - r.submit_s
            if self.on_tokens is not None or finished:
                if steps_h is None:
                    steps_h = np.asarray(self._state["steps"])
                out_h = np.asarray(self._state["out"])
            if self.on_tokens is not None:
                # stream tap: surface this round's new tokens per live
                # request (the host sync already landed, so the arrays are
                # final for the round; a row that finished mid-round stopped
                # at its true step count). Rides the same sync — no extra
                # round-trip boundary, just two host pulls the gateway
                # opted into.
                events = []
                for slot, r in slots.items():
                    n = int(steps_h[slot])
                    seen = self._emitted.get(r.request_id, 0)
                    if n > seen:
                        events.append((r.request_id,
                                       np.array(out_h[slot, seen:n])))
                        self._emitted[r.request_id] = n
                if events:
                    self.on_tokens(events)
        with tracing.span("engine.finish"):
            for slot in finished:
                r = slots.pop(slot)
                self._scanned.pop(slot, None)
                self._emitted.pop(r.request_id, None)
                if self.speculative:
                    self._draft_dirty.discard(slot)
                n = int(steps_h[slot])
                r.output = np.array(out_h[slot, :n])
                r.status = "done"
                r.finish_s = time.perf_counter()
                r.latency_s = r.finish_s - r.submit_s
                self.generated_tokens += n
                self._status_counts["done"] += 1
                self.scheduler.observe_service(r.priority,
                                               r.finish_s - r.admit_s)
                if r.deadline_s is not None:
                    self.scheduler.observe_deadline(
                        r.priority, r.latency_s <= r.deadline_s)
                self._cache_state = self.backend.free_slot(
                    self._cache_state, slot)
                free.append(slot)
                done[r.request_id] = r

    def kv_gauges(self) -> Dict[str, int]:
        """The KV allocator's gauges, stored on each ``engine.step`` span:
        ``kv_blocks_committed`` (blocks in use plus blocks promised to
        admitted requests and not yet drawn), ``kv_blocks_live`` (blocks
        holding written tokens, summed over decoding and prefilling
        slots) and ``slots_decoding``. The block gauges read 0 on the ring
        backend, which has no blocks."""
        b = self.backend
        committed = live = 0
        if hasattr(b, "blocks_committed"):
            bs = b.block_size
            committed = b.blocks_committed
            live = sum(-(-(len(r.prompt) + self._scanned[s]) // bs)
                       for s, r in self._slots.items())
            live += sum(-(-pp.next // bs) for pp in self._prefilling.values())
        return {"kv_blocks_committed": committed, "kv_blocks_live": live,
                "slots_decoding": len(self._slots)}

    # -- stats ----------------------------------------------------------------
    def occupancy(self) -> float:
        """Useful tokens per *scheduled* token-slot across executed plans:
        decode rounds schedule ``len(slots) × K`` token-slots (tokens a
        finished-mid-scan row doesn't produce are waste), prompt work
        schedules its padded bucket (pad columns are waste). The old
        ``occupied / (steps × batch_slots)`` denominator charged the engine
        for slots the workload (or a block-starved pool) could never fill —
        paged runs with a widened slot range misreported badly. Exact once
        the engine has drained (in-flight tokens count only at
        completion)."""
        useful = self.generated_tokens + self.useful_prefill_tokens
        return useful / max(self.planned_token_slots, 1)

    def hbm_bytes(self) -> int:
        """Device-resident KV-cache footprint of this engine (draft-model
        cache included when speculation is on — its ring lines are real
        HBM the operator pays for)."""
        total = self.backend.hbm_bytes()
        if self.speculative:
            total += self._draft_backend.hbm_bytes()
        return total

    def hbm_bytes_per_device(self) -> int:
        """Per-device KV footprint: on a mesh the pools split their KV-head
        dim ``kv_shards`` ways, so each device pays ``1/kv_shards`` of the
        K/V bytes (position slots and tables replicate). Equals
        ``hbm_bytes()`` without a mesh — and it's the quantity the
        ``sharded_decode`` bench holds fixed while scaling slots."""
        total = self.backend.hbm_bytes_per_device()
        if self.speculative:
            total += self._draft_backend.hbm_bytes_per_device()
        return total

    def assert_invariants(self) -> None:
        """Engine-level invariant sweep (tests call this mid-traffic):
        backend allocator accounting — extended to the live device pool,
        so per-shard byte conservation is checked against the host-global
        ledger — plus, on a mesh, placement coherence of the whole cache
        state (every leaf carries exactly the prescribed sharding, one
        equal-size shard per device)."""
        if hasattr(self.backend, "assert_invariants"):
            self.backend.assert_invariants(self._cache_state)
        if self.mesh is not None:
            assert_cache_placement(self.mesh, self._cache_state)
            if self.speculative:
                assert_cache_placement(self.mesh, self._draft_state)

    # -- durability -----------------------------------------------------------
    def note_hang(self) -> None:
        """Watchdog escalation: a dispatch exceeded its wall-clock deadline
        and the grace wait also expired-or-recovered-late. The stall raised
        nothing, so no exception path ran — synthesize the same recovery
        the raising seams get: roll every active slot back to its host
        checkpoint and requeue through the retry/backoff ladder. If the
        stalled dispatch did eventually land, the rollback discards real
        work, but the checkpoint (tokens + ``last`` logits + step counter)
        makes the resumed stream token-exact either way — wasted compute,
        never wrong tokens."""
        self.hang_recoveries += 1
        self._recover_decode_fault("hang")

    def _live_requests(self) -> List[Request]:
        """Every non-terminal request the engine owns, de-duplicated:
        queued (preempted/resuming included), mid-prefill, mid-decode."""
        live = list(self._queue)
        live.extend(pp.request for pp in self._prefilling.values())
        live.extend(self._slots.values())
        return live

    def known_request_ids(self) -> set:
        """Request ids this engine can account for — live or terminal.
        The gateway's journal replay consults this to decide which logged
        submissions were lost in a crash and must be re-queued."""
        ids = {r.request_id for r in self._live_requests()}
        ids.update(self._done.keys())
        return ids

    def snapshot(self) -> Dict[str, object]:
        """Serialize every request the engine owns — live and terminal —
        into a nested string-keyed dict fit for ``save_snapshot`` (flat
        key-path .npz via ``checkpoint.io``). Non-destructive: device
        state, slots and the block pool are untouched; live decode slots
        are checkpointed exactly the way preemption checkpoints them
        (generated tokens, step counter, ``last`` logits, and — on a
        paged backend — the slot's K/V blocks via ``checkpoint_slot``),
        so ``restore`` on a cold engine resumes token-for-token.

        Wall-clock stamps cross a process boundary, so ages are stored
        relative (``age_s = now - submit_s``) and re-anchored at restore.
        Stream-emission watermarks (``_emitted``) are deliberately *not*
        captured: after a crash-restart the gateway replays each stream
        from token zero."""
        now = time.perf_counter()
        requests: Dict[str, Dict[str, object]] = {}

        def base_meta(r: Request, phase: str, steps: int) -> dict:
            return {"rid": r.request_id, "phase": phase, "steps": steps,
                    "max_new_tokens": r.max_new_tokens,
                    "temperature": r.temperature, "priority": r.priority,
                    "deadline_s": r.deadline_s,
                    "age_s": now - r.submit_s if r.submit_s else 0.0,
                    "ttft_s": r.ttft_s, "preemptions": r.preemptions,
                    "status": r.status, "failure_reason": r.failure_reason,
                    "retries": r.retries, "last_fault": r.last_fault,
                    "downgraded": r.downgraded, "latency_s": r.latency_s}

        def record(r: Request, phase: str, steps: int,
                   tokens: Optional[np.ndarray],
                   last: Optional[np.ndarray], kv) -> None:
            rec: Dict[str, object] = {
                "meta": json_leaf(base_meta(r, phase, steps)),
                "prompt": np.asarray(r.prompt, np.int32)}
            if tokens is not None and len(tokens):
                rec["tokens"] = np.asarray(tokens, np.int32)
            if last is not None:
                rec["last"] = np.asarray(last, np.float32)
            if kv is not None:
                rec["kv"] = {"n_blocks": np.int32(kv["n_blocks"]),
                             "caches": resolve_swap_caches(kv)}
            requests[f"r{r.request_id:08d}"] = rec

        # live decode slots: host-pull the decode checkpoint wholesale
        if self._slots:
            steps_h = np.asarray(self._state["steps"])
            out_h = np.asarray(self._state["out"])
            last_h = np.asarray(self._state["last"])
            can_kv = self._preempt_swap and hasattr(self.backend,
                                                    "checkpoint_slot")
            for slot, r in self._slots.items():
                steps = int(steps_h[slot])
                kv = (self.backend.checkpoint_slot(self._cache_state, slot)
                      if can_kv else None)
                record(r, "live", steps, np.array(out_h[slot, :steps]),
                       np.array(last_h[slot]), kv)
        # mid-prefill and queued: the installed chunks are abandoned (the
        # restored engine re-prefills), but a carried resume checkpoint —
        # preempted or fault-requeued work — is preserved verbatim
        for r in list(self._queue) + [pp.request
                                      for pp in self._prefilling.values()]:
            rs = r.resume
            if rs is not None:
                record(r, "live", rs.steps, rs.tokens, rs.last, rs.kv)
            else:
                record(r, "live", 0, None, None, None)
        for r in self._done.values():
            rec: Dict[str, object] = {
                "meta": json_leaf(base_meta(r, "terminal", 0)),
                "prompt": np.asarray(r.prompt, np.int32)}
            if r.output is not None and len(r.output):
                rec["output"] = np.asarray(r.output, np.int32)
            requests[f"r{r.request_id:08d}"] = rec

        engine_meta = {"kind": type(self).__name__,
                       "backend": type(self.backend).__name__,
                       "next_id": self._next_id,
                       "step_count": self._step_count,
                       "status_counts": dict(self._status_counts),
                       "batch_slots": self.batch_slots,
                       "max_seq_len": self.max_seq_len,
                       "vocab": self.lm.cfg.padded_vocab}
        return {"engine": json_leaf(engine_meta), "requests": requests}

    def restore(self, snap: Dict[str, object]) -> Dict[str, int]:
        """Load a ``snapshot`` into this (cold) engine. Live requests
        re-enter the queue carrying their decode checkpoint as a
        ``_ResumeState`` — admission then resumes them through the exact
        swap/recompute machinery preemption uses, so survivors continue
        token-for-token (the same construction ``seed`` is required:
        sampling keys fold the base key with ``(rid, steps)``). A K/V
        checkpoint is kept only when this engine's backend can swap it
        back in; otherwise it is dropped and the recompute path rebuilds
        the cache from the host token stream — still exact. Terminal
        requests land straight in the done map so results survive the
        restart. Scheduler estimates are reset: pre-crash service-rate
        and deadline-hit history describes a process that no longer
        exists."""
        if self._slots or self._prefilling or self._queue or self._done:
            raise RuntimeError("restore() needs a cold engine: this one "
                               "already owns requests")
        eng = json_unleaf(snap["engine"])
        if eng.get("vocab") != self.lm.cfg.padded_vocab:
            raise ValueError(
                f"snapshot vocab {eng.get('vocab')} != engine vocab "
                f"{self.lm.cfg.padded_vocab}: the saved logits checkpoints "
                f"cannot be restored into this model")
        if eng.get("max_seq_len") != self.max_seq_len:
            raise ValueError(
                f"snapshot max_seq_len {eng.get('max_seq_len')} != engine "
                f"max_seq_len {self.max_seq_len}")
        now = time.perf_counter()
        can_kv = hasattr(self.backend, "swap_in")
        kv_template = (self._cache_state.get("caches")
                       if can_kv and isinstance(self._cache_state, dict)
                       else None)
        live = terminal = 0
        for key in sorted(snap["requests"]):
            rec = snap["requests"][key]
            meta = json_unleaf(rec["meta"])
            r = Request(int(meta["rid"]),
                        np.asarray(rec["prompt"], np.int32),
                        int(meta["max_new_tokens"]),
                        float(meta["temperature"]),
                        priority=int(meta["priority"]),
                        deadline_s=meta["deadline_s"])
            r.submit_s = now - float(meta["age_s"])
            r.ttft_s = float(meta["ttft_s"])
            r.preemptions = int(meta["preemptions"])
            r.retries = int(meta["retries"])
            r.last_fault = meta["last_fault"]
            r.downgraded = bool(meta["downgraded"])
            if meta["phase"] == "terminal":
                r.status = meta["status"]
                r.failure_reason = meta["failure_reason"]
                r.latency_s = float(meta["latency_s"])
                r.finish_s = now
                out = rec.get("output")
                r.output = (np.asarray(out, np.int32) if out is not None
                            else np.zeros((0,), np.int32))
                self._done[r.request_id] = r
                terminal += 1
                continue
            steps = int(meta["steps"])
            if steps > 0:
                kv = None
                if can_kv and "kv" in rec and kv_template is not None:
                    kv = {"n_blocks": int(np.asarray(
                              rec["kv"]["n_blocks"])),
                          "caches": _rebuild_like(kv_template,
                                                  rec["kv"]["caches"])}
                tokens = rec.get("tokens")
                r.resume = _ResumeState(
                    steps=steps,
                    tokens=(np.asarray(tokens, np.int32)
                            if tokens is not None
                            else np.zeros((0,), np.int32)),
                    last=np.asarray(rec["last"], np.float32),
                    kv=kv)
            self._queue.append(r)
            live += 1
        self._queue.sort(key=request_rank)
        self._next_id = max(self._next_id, int(eng["next_id"]))
        self._step_count = max(self._step_count, int(eng["step_count"]))
        self._status_counts.update(eng["status_counts"])
        self.scheduler.reset_estimates()
        self.restores += 1
        return {"live": live, "terminal": terminal}

    def requeue_lost(self, request_id: int, prompt: np.ndarray,
                     max_new_tokens: int = 16, temperature: float = 0.0,
                     priority: int = 0,
                     deadline_s: Optional[float] = None) -> Request:
        """Journal replay: re-queue a submission the crash lost (it was
        acknowledged but appears in no snapshot), under its *original*
        request id so the client's handle and the journal's terminal
        record still line up. Generation starts over from the prompt —
        nothing survived to resume from."""
        prompt = validate_prompt(prompt, max_new_tokens, self.max_seq_len,
                                 self.truncate_prompts)
        r = Request(int(request_id), prompt, max_new_tokens, temperature,
                    priority=priority, deadline_s=deadline_s)
        r.submit_s = time.perf_counter()
        self._next_id = max(self._next_id, int(request_id) + 1)
        self._queue.append(r)
        return r


def _rebuild_like(template, loaded):
    """Rebuild ``loaded`` (nested string-keyed dicts from
    ``load_checkpoint_tree``) into the pytree *structure* of ``template``.
    ``flat_paths`` spells a list index and a same-named dict key
    identically ("caches/0/..."), so matching the flat paths and
    unflattening against the template's treedef recovers the original
    container types — which the jitted swap-in scatter was traced
    against."""
    tpl = flat_paths(template)
    got = flat_paths(loaded)
    missing = set(tpl) - set(got)
    if missing:
        raise ValueError(f"snapshot K/V missing paths: "
                         f"{sorted(missing)[:5]}")
    return jax.tree.unflatten(jax.tree.structure(template),
                              [got[k] for k in tpl])


def save_snapshot(directory: str, snapshot: Dict[str, object],
                  step: int = 0, keep: int = 3) -> str:
    """Persist an engine snapshot through the checkpoint envelope (atomic
    rename, bounded retention)."""
    return save_checkpoint(directory, step, snapshot, keep=keep)


def load_snapshot(directory: str, step: Optional[int] = None):
    """Load a persisted engine snapshot (template-free): returns
    ``(snapshot_tree, step)``."""
    return load_checkpoint_tree(directory, step)


class DrainBatchEngine:
    """The previous static batcher, kept as the measured baseline: drain the
    queue in fixed batches padded to the longest prompt (one prefill compile
    per distinct length), decode everyone for the longest budget, and sample
    on the host every token."""

    def __init__(self, lm: LM, params, *, batch_slots: int = 8,
                 max_seq_len: int = 512, seed: int = 0,
                 truncate_prompts: bool = False):
        if lm.cfg.frontend.kind == "audio":
            raise NotImplementedError("engine serves text-token streams")
        self.lm = lm
        self.params = params
        self.batch_slots = batch_slots
        self.max_seq_len = max_seq_len
        self.truncate_prompts = truncate_prompts
        self.rng = jax.random.PRNGKey(seed)
        self._queue: List[Request] = []
        self._next_id = 0
        self.generated_tokens = 0
        self.host_syncs = 0     # one logits round-trip per decoded token

        windowed = _has_windowed_blocks(lm)

        def prefill(params, batch, lengths):
            # lengths matter only when a window-wide cache could keep pad
            # rows of the batch's longest-prompt padding (see _admit_impl)
            return lm.prefill(params, batch, cache_width=max_seq_len,
                              lengths=lengths if windowed else None)

        self.prefill_fn = jax.jit(prefill)
        self.decode_fn = jax.jit(lm.decode_step)

    def submit(self, prompt: np.ndarray, max_new_tokens: int = 16,
               temperature: float = 0.0, priority: int = 0,
               deadline_s: Optional[float] = None) -> int:
        """Queue a request. ``priority``/``deadline_s`` are recorded for
        per-class reporting but the drain batcher stays strictly FIFO —
        it is the measured baseline, not an SLO policy."""
        prompt = validate_prompt(prompt, max_new_tokens, self.max_seq_len,
                                 self.truncate_prompts)
        rid = self._next_id
        self._next_id += 1
        r = Request(rid, prompt, max_new_tokens, temperature,
                    priority=priority, deadline_s=deadline_s)
        r.submit_s = time.perf_counter()
        self._queue.append(r)
        return rid

    def run(self) -> Dict[int, Request]:
        done: Dict[int, Request] = {}
        while self._queue:
            batch = self._queue[:self.batch_slots]
            self._queue = self._queue[self.batch_slots:]
            self._serve_batch(batch)
            for r in batch:
                done[r.request_id] = r
        return done

    def _serve_batch(self, requests: List[Request]) -> None:
        b = self.batch_slots
        admit = time.perf_counter()          # batch enters service together
        for r in requests:
            r.admit_s = admit
        plen = max(len(r.prompt) for r in requests)
        lens = np.array([len(r.prompt) for r in requests]
                        + [plen] * (b - len(requests)), np.int32)
        tokens = np.zeros((b, plen), np.int32)
        for i, r in enumerate(requests):
            tokens[i, :len(r.prompt)] = r.prompt         # right-pad (exact)
        logits, caches = self.prefill_fn(self.params,
                                         {"tokens": jnp.asarray(tokens)},
                                         jnp.asarray(lens))
        last = jnp.take_along_axis(
            logits, jnp.asarray(lens)[:, None, None] - 1, axis=1)[:, 0, :]
        max_new = max(r.max_new_tokens for r in requests)
        outs = np.zeros((b, max_new), np.int32)
        pos = jnp.asarray(lens)
        temp = jnp.asarray([r.temperature for r in requests]
                           + [0.0] * (b - len(requests)), jnp.float32)
        for t in range(max_new):
            self.rng, k = jax.random.split(self.rng)
            nxt = sample_logits_batch(k, last, temp)
            outs[:, t] = np.asarray(nxt)[:b]             # per-token host trip
            self.host_syncs += 1
            if t == 0:
                first = time.perf_counter()
                for r in requests:
                    r.ttft_s = first - r.submit_s
            logits1, caches = self.decode_fn(self.params, caches,
                                             nxt[:, None], pos)
            pos = pos + 1
            last = logits1[:, 0, :]
        finish = time.perf_counter()
        for i, r in enumerate(requests):
            r.output = outs[i, :r.max_new_tokens]
            r.status = "done"
            r.finish_s = finish
            r.latency_s = finish - r.submit_s
            self.generated_tokens += r.max_new_tokens
