"""Token-budget scheduler: chunked prefill + decode packed into one jitted
mixed step per tick (continuous batching without pool-freezing B=1 prefill).

The legacy Engine (serve.engine) admits a request by running its whole
prompt as a separate B=1 prefill call: every distinct prompt length is its
own jit cache entry, and while a prompt compiles/runs every decode slot
head-of-line blocks. The Scheduler instead splits prompts into
``rc.prefill_chunk``-token chunks and packs chunks + decode rows into ONE
fixed-shape step of ``(max_batch, prefill_chunk)`` tokens per tick — one
compile for the whole serving lifetime, decode rows never stall behind
admissions, and the per-tick token budget (``rc.token_budget``) bounds tail
latency under bursts.

Each step carries a :class:`~repro.models.KVView`: per-row write position
``pos[b]``, per-row live width ``lens[b]`` (decode row = 1, prefill chunk
≤ chunk width, idle row = 0), and — under ``rc.kv_layout="paged"`` — the
block tables of serve.cache.BlockManager. Idle/padded columns write to a
trash location and their outputs are never read; logits are gathered at
column ``lens[b]-1`` per row.

Cycle attribution (``track_energy=True``): a tick's pool-wide tuGEMM cycles
are split across scheduled rows by **active-token weighting**
(``lens[b] / sum(lens)``) — superseding the legacy engine's "split evenly"
rule, which is only correct when every active row processes the same number
of tokens. For decode-only ticks the two rules coincide; with prefill
chunks in the batch the even split would overcharge decode rows by up to
``chunk×``. Per-row exact attribution still does not exist in the hardware
(the GEMM M axis is the packed pool and the unit drains max-over-rows);
token weighting is the documented approximation.

Robustness (DESIGN.md §10): admission flows through
``serve.admission.AdmissionController`` (priority classes, tenant budgets,
per-request tick deadlines, bounded queues), overload walks ONE ordered
``DegradationLadder`` (degrade spec-γ → shrink prefill budget → preempt
lowest-priority-youngest → shed expired/batch → reject admissions), and the
whole state is observable via :meth:`Scheduler.health`. A seed-keyed
``serve.faults.FaultPlan`` can induce allocation failures, preemption
storms, draft staleness, and NaN logits against the scheduler's logical
``clock``; a numerical guard quarantines any slot whose step logits go
non-finite, retries it clean, and escalates to a ``rc.fallback_policy``
(bf16) step if the fault persists. Faults change *scheduling*, never
*results* (tests/test_chaos.py).
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig, RunConfig
from ..core.report import slot_energy
from ..models import KVView, forward, init_caches, lm_logits
from ..models.transformer import plan_groups
from ..obs.logs import kv
from ..obs.metrics import MetricsRegistry, family_percentile as _family_percentile
from ..obs.profile import compile_count, named_scope, watch_compiles
from ..obs.trace import NULL_TRACER, PID_REQUESTS, PID_SCHED, TID_TICK
from ..parallel.sharding import current_ctx as sharding_ctx
from ..quant import capture as stats_capture
from ..quant.capture import tree_totals_by_bits
from .admission import (
    LADDER_LEVELS,
    PRIORITY_RANK,
    AdmissionController,
    DegradationLadder,
    Rejection,
    RejectReason,
)
from .cache import BlockManager, num_pages_for

__all__ = [
    "Request",
    "SlotMeter",
    "Scheduler",
    "build_mixed_step",
    "install_sigint_drain",
    "request_keys",
    "sample",
]

log = logging.getLogger("repro.serve")


# PRNG stream tags folded into per-request keys: the token sampled at one
# sequence position must draw from a different stream than the speculative
# machinery's draws *about* that position (serve/spec.py), or acceptance
# thresholds would be correlated with the tokens they judge.
STREAM_SAMPLE = 0    # the canonical next-token draw at a position
STREAM_DRAFT = 1     # draft-model proposal draw
STREAM_ACCEPT = 2    # rejection-sampling acceptance uniform
STREAM_RESIDUAL = 3  # residual-distribution draw after a rejection


def request_keys(
    base_key, rids, positions, stream: int = STREAM_SAMPLE
) -> jnp.ndarray:
    """Deterministic per-row PRNG keys: ``fold_in(base, rid, position,
    stream)`` for each row. ``positions`` are absolute sequence indices of
    the token being drawn, so a request's random stream depends only on
    (seed, rid, position) — never on how the scheduler happened to pack
    ticks. Temperature>0 runs are reproducible across batch sizes, arrival
    orders, and recompute preemptions (the re-sampled token at a replayed
    position reuses its original key)."""
    rids = jnp.asarray(rids, jnp.int32)
    positions = jnp.asarray(positions, jnp.int32)
    keys = jax.vmap(lambda r: jax.random.fold_in(base_key, r))(rids)
    keys = jax.vmap(jax.random.fold_in)(keys, positions)
    return jax.vmap(lambda k: jax.random.fold_in(k, stream))(keys)


def sample(key, logits: jnp.ndarray, temperature: float = 0.0) -> jnp.ndarray:
    """Greedy argmax at temperature<=0 (key unused). Otherwise a categorical
    draw: with a single key, one batched draw (legacy engine); with a stack
    of per-row keys (``request_keys``, key.ndim == logits.ndim) each row
    draws from its own stream."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if getattr(key, "ndim", 1) == 2:  # stacked per-row keys (B, key_data)
        return jax.vmap(
            lambda k, l: jax.random.categorical(k, l / temperature, axis=-1)
        )(key, logits).astype(jnp.int32)
    return jax.random.categorical(key, logits / temperature, axis=-1).astype(jnp.int32)


@dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new: int = 32
    out: list[int] = field(default_factory=list)
    done: bool = False
    # robustness metadata (serve/admission.py). ``priority`` is one of
    # realtime | interactive | batch; ``ttl_ticks`` is a deadline relative to
    # submission on the scheduler's logical clock (None = no deadline);
    # ``tenant`` keys per-tenant token budgets. Terminal state is exactly one
    # of ``done`` (completed) or ``rejected`` (a structured
    # admission.Rejection) — never silence.
    tenant: str = "default"
    priority: str = "interactive"
    ttl_ticks: int | None = None
    deadline: int | None = None      # absolute clock deadline (set at submit)
    submitted_tick: int = 0
    admitted: bool = False           # ever held a slot (preemption re-queues stay True)
    rejected: Rejection | None = None
    # tenant accounting (serve/admission.py): ``charged`` is the quote
    # debited at submit (len(prompt) + max_new, 0 when the tenant has no
    # budget); ``prompt_consumed`` high-water-marks how many *original*
    # prompt tokens have been committed to KV (generated tokens live in
    # ``out``); ``settled`` guards the terminal one-shot refund of the
    # unconsumed remainder.
    charged: int = 0
    prompt_consumed: int = 0
    settled: bool = False

    def consumed_tokens(self) -> int:
        """Tokens this request actually used against its tenant quote:
        prompt tokens committed (prefilled or prefix-cache reused — both are
        served tokens) plus every token generated. Recompute-preemption
        re-prefills are deliberately NOT double-counted: the quote is a cap
        on service delivered, not on engine work performed."""
        return self.prompt_consumed + len(self.out)


@dataclass
class SlotMeter:
    """Per-request tuGEMM hardware accounting across prefill + decode.

    Cycles are bucketed **per bitwidth**: under a mixed QuantPolicy the
    int8 attention cycles and int2 MLP cycles of one request run at
    different clocks and Table-I power points, so they must be kept apart
    until the final latency/energy conversion."""

    rid: int
    prompt_tokens: int = 0
    decode_tokens: int = 0
    # prompt tokens served from the prefix cache (DESIGN.md §11): their KV
    # was forked from shared pages, so they were never scheduled into a
    # prefill chunk and are charged ZERO cycles — this counter is the
    # explicit record of that delta (the only meter difference vs an
    # uncached run of the same trace).
    cached_prompt_tokens: int = 0
    # tokens actually emitted so far (decode tokens + the prefill-riding
    # first token once it exists) — exact even mid-prefill, unlike deriving
    # it from prompt_tokens
    emitted_tokens: int = 0
    # speculative decoding (serve/spec.py): proposals this request drafted,
    # and how many of them the target verified and kept. Rejected drafts'
    # compute is NOT subtracted anywhere — their cycles stay in the buckets
    # below, so energy-per-accepted-token honestly includes the waste.
    drafted_tokens: int = 0
    accepted_draft_tokens: int = 0
    # bits -> cycles; prefill exact ints (legacy B=1 prefill), shared-step
    # cycles accumulate in float (a step's pool-wide total times this slot's
    # active-token weight is fractional); rounding happens once at read so
    # the meters stay conservative: sum over slots == measured pool totals.
    # Draft-pass cycles are kept apart from target cycles: under spec
    # decoding the draft runs a *different* QuantPolicy (e.g. int2), and the
    # accepted-tokens/J report needs the draft-vs-verify energy split.
    prefill_by_bits: dict = field(default_factory=dict)   # bits -> {variant: int}
    decode_by_bits: dict = field(default_factory=dict)    # bits -> {variant: float}
    draft_by_bits: dict = field(default_factory=dict)     # bits -> {variant: float}

    def add_prefill(self, by_bits: dict) -> None:
        for b, tot in by_bits.items():
            d = self.prefill_by_bits.setdefault(b, {"serial": 0, "parallel": 0})
            d["serial"] += tot["serial_cycles"]
            d["parallel"] += tot["parallel_cycles"]

    def add_share(self, by_bits: dict, weight: float, *, bucket: str = "decode") -> None:
        """Charge ``weight`` (this slot's active-token fraction) of one
        step's pool-wide cycles to this request. ``bucket="draft"`` routes
        to the draft-pass accounting (cycles at the draft policy's
        bitwidths); the default is the target-policy bucket (decode +
        spec-verify steps)."""
        dst = self.draft_by_bits if bucket == "draft" else self.decode_by_bits
        for b, tot in by_bits.items():
            d = dst.setdefault(b, {"serial": 0.0, "parallel": 0.0})
            d["serial"] += tot["serial_cycles"] * weight
            d["parallel"] += tot["parallel_cycles"] * weight

    def add_decode_share(self, by_bits: dict, active: int) -> None:
        """Legacy even split — every active row decodes exactly one token,
        so 1/active IS the active-token weight."""
        self.add_share(by_bits, 1.0 / active)

    def cycles_by_bits(
        self, variant: str = "serial", *, bucket: str | None = None
    ) -> dict[int, int]:
        """Total cycles per bitwidth. ``bucket`` selects one accounting
        bucket ("prefill" | "decode" | "draft"); None sums all three."""
        srcs = {
            "prefill": self.prefill_by_bits,
            "decode": self.decode_by_bits,
            "draft": self.draft_by_bits,
        }
        picked = srcs.values() if bucket is None else (srcs[bucket],)
        out: dict[int, int] = {}
        for src in picked:
            for b, d in src.items():
                out[b] = out.get(b, 0) + int(round(d[variant]))
        return out

    def cycles(self, variant: str = "serial") -> int:
        return sum(self.cycles_by_bits(variant).values())

    def energy(self, variant: str = "serial", *, bits: int | None = None) -> dict:
        """Latency/energy of this request's GEMM work on the paper's 16×16
        unit (time-multiplexed across slots). ``bits`` forces the legacy
        uniform accounting; the default charges each bucket at its own
        clock/power. Under speculative decoding ``energy_j`` includes the
        draft pass and every rejected candidate's verify cycles — the
        ``draft_*`` fields expose the split."""
        by = self.cycles_by_bits(variant)
        lat = e_j = 0.0
        for b, cyc in by.items():
            l, e = slot_energy(bits if bits is not None else b, variant, cyc)
            lat += l
            e_j += e
        draft_by = self.cycles_by_bits(variant, bucket="draft")
        draft_e = 0.0
        for b, cyc in draft_by.items():
            draft_e += slot_energy(bits if bits is not None else b, variant, cyc)[1]
        out = {
            "rid": self.rid,
            "tokens": self.prompt_tokens + self.decode_tokens,
            "generated_tokens": self.emitted_tokens,
            "cycles": sum(by.values()),
            "cycles_by_bits": by,
            "latency_s": lat,
            "energy_j": e_j,
        }
        if self.drafted_tokens or draft_by:
            out.update(
                drafted_tokens=self.drafted_tokens,
                accepted_draft_tokens=self.accepted_draft_tokens,
                draft_cycles_by_bits=draft_by,
                draft_energy_j=draft_e,
                target_energy_j=e_j - draft_e,
            )
        return out


# ------------------------------------------------------------------- step fn
def build_mixed_step(
    cfg: ModelConfig,
    rc: RunConfig,
    *,
    with_stats: bool = False,
    all_logits: bool = False,
    scope: str = "serve/step",
):
    """One tick: (params, caches, tokens (B,W), pos (B,), lens (B,), tables)
    -> (caches, logits[, stats]).

    Decode rows use column 0 (lens=1), prefill chunks up to W columns,
    idle rows lens=0. By default row b's logits come from hidden column
    lens[b]-1 — the next-token distribution after its last real token —
    and the step returns (B, V). ``all_logits=True`` keeps *every* chunk
    column's next-token distribution, returning (B, W, V): the speculative
    verify step (serve/spec.py) judges all γ+1 candidate positions of a
    row from one chunked-prefill-shaped pass, so no position may be
    discarded. Padded columns (>= lens[b]) carry garbage — callers mask by
    lens exactly as the KV write path does."""

    def step(params, caches, tokens, pos, lens, tables):
        view = KVView(
            pos=pos, lens=lens, tables=tables,
            block_size=rc.block_size, layout=rc.kv_layout,
        )
        batch = {"tokens": tokens}
        if cfg.mrope_sections is not None:
            B, S = tokens.shape
            p = pos[:, None] + jnp.broadcast_to(
                jnp.arange(S, dtype=jnp.int32), (B, S)
            )
            batch["positions"] = jnp.stack([p, p, p])
        with named_scope(scope):
            h, caches, _ = forward(
                cfg, rc, params, batch, caches=caches, cache_pos=pos, kv_view=view
            )
            with named_scope("serve/logits"):
                if all_logits:
                    return caches, lm_logits(cfg, rc, params, h)   # (B, W, V)
                idx = jnp.clip(lens - 1, 0, tokens.shape[1] - 1)
                h_last = jnp.take_along_axis(h, idx[:, None, None], axis=1)
                logits = lm_logits(cfg, rc, params, h_last)        # (B,1,V)
        return caches, logits[:, 0, :]

    if not with_stats:
        return step

    def step_stats(params, caches, tokens, pos, lens, tables):
        with stats_capture.capture_stats() as cap:
            caches, logits = step(params, caches, tokens, pos, lens, tables)
        return caches, logits, cap.tree

    return step_stats


# ----------------------------------------------------------------- scheduler
@dataclass
class _Slot:
    req: Request
    prompt: list[int]            # effective prompt: original + tokens already
    #                              generated before a recompute-preemption
    admit_seq: int = 0           # admission order (preemption picks youngest)
    pos: int = 0                 # tokens already written to this row's cache
    last_token: int = 0          # next decode input (last sampled token)
    meter: SlotMeter | None = None
    # speculative decoding (serve/spec.py): tokens already written to this
    # row of the *draft* KV pool, plus the committed sequence tokens the
    # draft has not ingested yet (draft_pos + len(draft_gap) == pos at tick
    # boundaries). The gap is normally 0 or 1 token — exactly the previous
    # tick's last accepted candidate when all γ were accepted — and is
    # bounded by γ: a slot that falls further behind (repeated pool-pressure
    # ticks with no draft budget) goes draft_stale and plain-decodes rather
    # than growing unbounded catch-up state. Once the ladder is healthy
    # again the scheduler re-syncs the draft pool in chunk-width passes
    # (committed tokens re-ingested at the draft width) and clears the flag.
    draft_pos: int = 0
    draft_gap: list[int] = field(default_factory=list)
    draft_stale: bool = False
    # numerical-fault quarantine (DESIGN.md §10): consecutive non-finite
    # logits strikes, and whether the row has been switched to the fallback
    # (bf16-policy) step. Fallback is sticky — a model that NaNs at low bits
    # will NaN again, so ping-ponging back would just burn retry ticks.
    retries: int = 0
    fallback: bool = False
    # prefix cache: committed full blocks of this slot already indexed in
    # the trie (registration resumes past them; forked blocks count from
    # admission, so a forked slot never re-registers what it borrowed)
    reg_blocks: int = 0

    @property
    def prefilling(self) -> bool:
        return self.pos < len(self.prompt)


# Legacy plain-int Scheduler counters, now registry-backed (DESIGN.md §14).
# Each becomes a class-level property over a ``serve_<attr>_total`` Counter:
# the historical ``self.x += 1`` / ``self.x = 0`` write sites keep working,
# while Prometheus/JSONL export and health() read the same storage.
_SCHED_COUNTERS = {
    "generated_tokens": "tokens emitted (decode + prefill-riding first tokens)",
    "drafted_tokens": "speculative proposals drafted",
    "accepted_draft_tokens": "drafted tokens the target verified and kept",
    "ticks": "tick() calls that ran a device step",
    "preemptions": "slots evicted under pool pressure (recompute-on-resume)",
    "prefix_hits": "admissions that forked a cached prefix",
    "prefix_tokens_reused": "prompt tokens served from shared pages",
    "prefill_tokens_computed": "prompt tokens actually stepped",
    "deadline_misses": "completions past their deadline",
    "stalled_rows_total": "row-ticks lost to pool exhaustion",
    "stall_episodes": "distinct pool-pressure episodes",
    "engine_stalls": "unexplained no-progress ticks (must stay 0)",
    "idle_fault_ticks": "ticks idled by injected allocation exhaustion",
    "nan_events": "non-finite logit rows quarantined",
    "fallback_retries": "rows escalated to the fallback-policy step",
    "draft_stale_events": "slots entering draft staleness",
    "draft_resyncs": "stale slots recovered via draft resync",
    "moe_dropped_tokens": "router capacity drops (never silent)",
}


def _counter_property(attr: str):
    def fget(self):
        v = self._ctr[attr].value
        return int(v) if float(v).is_integer() else v

    def fset(self, v):
        self._ctr[attr].value = v

    return property(fget, fset)


class Scheduler:
    """Block-managed, continuously-batched serving engine.

    One jitted mixed step of static shape ``(max_batch, prefill_chunk)``
    serves prefill and decode alike; the per-tick plan fills rows under a
    token budget with decode rows first (no starvation), then prompt
    chunks in FIFO order. ``rc.kv_layout`` selects dense per-row buffers
    (bit-exact A/B baseline) or the paged pool + BlockManager."""

    def __init__(
        self,
        cfg: ModelConfig,
        rc: RunConfig,
        params: dict,
        *,
        capacity: int,
        max_batch: int,
        num_pages: int | None = None,
        temperature: float = 0.0,
        seed: int = 0,
        track_energy: bool = False,
        draft_params: dict | None = None,
        admission: AdmissionController | None = None,
        faults=None,
        mesh=None,
        tracer=None,
        metrics: MetricsRegistry | None = None,
    ):
        for g in plan_groups(cfg):
            for kind in g.kinds:
                if kind.mixer in ("ssm", "hybrid"):
                    raise NotImplementedError(
                        "chunked-prefill scheduling needs resumable mixer state; "
                        "SSM/hybrid stacks serve through the legacy Engine"
                    )
        self.cfg, self.rc, self.params = cfg, rc, params
        self.capacity, self.max_batch = capacity, max_batch
        self.chunk = max(rc.prefill_chunk, 1)
        self.token_budget = rc.token_budget or max_batch * self.chunk
        self.temperature = temperature
        self.key = jax.random.PRNGKey(seed)
        self.track_energy = track_energy

        # --- observability (DESIGN.md §14) ------------------------------
        # ``self.trace`` is NULL_TRACER when tracing is off: every call site
        # guards arg construction on ``self.trace.enabled`` so a disabled
        # tracer costs one attribute load per tick phase. ``self.metrics``
        # always exists — the plain-int counters this class used to carry
        # are now class-level properties backed by registry Counters (the
        # ~30 existing ``self.x += 1`` write sites work unchanged), so
        # health() is a registry view and Prometheus/JSONL export is free.
        self.trace = tracer if tracer is not None else NULL_TRACER
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self._init_metrics()
        if self.trace.enabled:
            self.trace.name_process(PID_SCHED, "scheduler")
            self.trace.name_thread(PID_SCHED, TID_TICK, "tick")
            self.trace.name_process(PID_REQUESTS, "requests")
        # kernel path counters are process-global (jit trace-time events);
        # snapshot at construction so health() reports only THIS engine's
        # trace activity (kernels/ops.kernel_counters_since) — two
        # back-to-back schedulers must not see each other's counts.
        from ..kernels import ops as _kops
        self._kops = _kops
        self._kernel_base = _kops.kernel_counters()
        # backend compiles are process-global too: count from here
        self._compile_base = watch_compiles(self.trace)
        self._t_submit: dict[int, float] = {}    # rid -> wall time at submit
        self._t_queued: dict[int, float] = {}    # rid -> tracer ts at enqueue
        self._t_emit: dict[int, float] = {}      # rid -> wall time, last emit
        self._tick_energy_j = 0.0                # modeled J this tick
        self._total_energy_j = 0.0               # modeled J since construction

        self.paged = rc.kv_layout == "paged"
        self.prefix_caching = bool(getattr(rc, "prefix_cache", False))
        if self.prefix_caching and not self.paged:
            raise ValueError(
                "rc.prefix_cache needs rc.kv_layout='paged' — prefix sharing "
                "is page aliasing; the dense layout has nothing to alias"
            )
        if self.paged:
            pages = (
                num_pages
                if num_pages is not None
                else num_pages_for(capacity, rc.block_size, max_batch)
            )
            self.mgr: BlockManager | None = BlockManager(
                pages, rc.block_size, max_batch, capacity,
                prefix_cache=self.prefix_caching,
            )
            self.mgr.bind_registry(self.metrics)
            self.caches = init_caches(cfg, rc, max_batch, capacity, num_pages=pages)
        else:
            self.mgr = None
            self.caches = init_caches(cfg, rc, max_batch, capacity)

        # sharded serving (parallel/serve_mesh.py, DESIGN.md §12): the same
        # mixed step shard_map-ped over a (dp, tp) mesh. The planner,
        # BlockManager and every host loop below stay device-agnostic — the
        # mesh only changes where the step's arrays live and how the stats
        # tree is merged. The allocator is deliberately NOT sharded: one
        # authoritative host-global page table, uploaded version-keyed.
        self.mesh = None
        self._mesh_step = None
        self._fb_handle = None
        self._shard_ctx = sharding_ctx()    # for health(): dropped rules etc.
        self.moe_dropped_tokens = 0         # router capacity drops (never silent)
        self.comms: dict = {}               # (label, bits) -> byte totals
        self.cycles_by_bits: dict = {}      # bits -> exact int cycle totals
        self._device_weight: dict = {}      # bits -> (dp, tp) int64 serial load
        if mesh is not None:
            from ..parallel import serve_mesh as _sm

            if getattr(rc, "spec_gamma", 0) > 0:
                raise NotImplementedError(
                    "speculative decoding on a mesh is not supported yet — "
                    "the draft pool fork/rollback protocol is single-device"
                )
            self.mesh = _sm.as_spec(mesh)
            _sm.validate(cfg, rc, self.mesh, max_batch)
            self.params = _sm.shard_params(self.mesh, self.params)
            self.caches = _sm.shard_caches(self.mesh, rc, self.caches)
            self._mesh_step = _sm.build_sharded_step(
                cfg, rc, self.mesh, self.params, self.caches,
                with_stats=track_energy,
            )
            self._step = self._mesh_step
        else:
            self._step = jax.jit(
                build_mixed_step(cfg, rc, with_stats=track_energy), donate_argnums=(1,)
            )
        # speculative decoding: a draft-policy model view + draft KV pool
        # (serve.spec.SpecDecoder) and a verify-shaped target step that keeps
        # every chunk column's logits. All spec-mode ticks route through
        # _spec_tick; spec_gamma == 0 leaves the plain path byte-for-byte.
        self.spec = None
        if getattr(rc, "spec_gamma", 0) > 0:
            from .spec import SpecDecoder

            self.spec = SpecDecoder(
                cfg, rc, params,
                max_batch=max_batch, capacity=capacity,
                num_pages=self.mgr.num_pages if self.mgr is not None else None,
                track_energy=track_energy, draft_params=draft_params,
            )
            self._vstep = jax.jit(
                build_mixed_step(cfg, rc, with_stats=track_energy,
                                 all_logits=True, scope="serve/verify"),
                donate_argnums=(1,),
            )
        self.slots: list[_Slot | None] = [None] * max_batch
        self.finished: list[Request] = []
        self.finished_meters: list[SlotMeter] = []
        self.final_kv_lens: dict[int, int] = {}   # rid -> live KV at finish
        self.generated_tokens = 0
        self.drafted_tokens = 0
        self.accepted_draft_tokens = 0
        self.ticks = 0
        self.preemptions = 0
        self._admit_counter = 0
        self._meters_by_rid: dict[int, SlotMeter] = {}
        self._tables_dev = None          # device copy of mgr.tables ...
        self._tables_version = -1        # ... keyed on mgr.version
        self._rr = 0                     # rotating plan start (fairness)
        # optional callable(rids, logits): each non-speculative tick's
        # scheduled request ids and a copy of their (len(rids), V) f32
        # logits, before sampling (e.g. to compare two engines' numerics)
        self.logits_hook = None

        # --- prefix cache (DESIGN.md §11) ---
        self.prefix_hits = 0             # admissions that forked a cached prefix
        self.prefix_tokens_reused = 0    # prompt tokens served without prefill
        self.prefill_tokens_computed = 0 # prompt tokens actually stepped
        self._cow_jit = None             # lazily-built shared-page copy step

        # --- robustness layer (DESIGN.md §10) ---
        self.admission = admission if admission is not None else AdmissionController()
        self.ladder = DegradationLadder(relax_after=rc.ladder_relax_ticks)
        self.faults = faults             # serve.faults.FaultPlan | None
        self.clock = 0                   # logical time: +1 per tick() call,
        #                                  even idle ones — deadlines and
        #                                  fault plans key on it
        self.draining = False            # graceful shutdown: no new admissions
        self.deadline_misses = 0         # completions past their deadline
        self.stalled_rows_total = 0      # row-ticks lost to pool exhaustion
        self.stall_episodes = 0          # distinct pressure episodes
        self._in_stall = False
        self.engine_stalls = 0           # active slots + nothing schedulable
        #                                  + no injected fault (must stay 0)
        self.idle_fault_ticks = 0        # ticks idled by injected exhaustion
        self.nan_events = 0              # non-finite logit rows quarantined
        self.fallback_retries = 0        # rows escalated to the bf16 step
        self.draft_stale_events = 0      # slots entering draft staleness
        self.draft_resyncs = 0           # stale slots recovered via resync
        self.nan_retry_limit = 1         # clean retries before bf16 fallback
        self._fault_fired = False        # injected alloc failure this tick
        self._stall_this_tick = False
        self._fb_step = None             # lazily-built fallback-policy step
        self._fb_unavailable = False
        if self.mgr is not None and self.faults is not None:
            self.mgr.fault_hook = self._alloc_fault_hook
        # re-home the admission controller's counters onto this registry so
        # one scrape covers the whole engine (its handles are re-fetched)
        self.admission.bind_registry(self.metrics)
        self._register_gauges()

    # ---------------------------------------------------------- observability
    def _init_metrics(self) -> None:
        m = self.metrics
        self._ctr = {
            a: m.counter(f"serve_{a}_total", h)
            for a, h in _SCHED_COUNTERS.items()
        }
        self._h_ttft = m.histogram(
            "serve_ttft_seconds",
            "wall time from submit to first emitted token", labels=("priority",))
        self._h_itl = m.histogram(
            "serve_itl_seconds",
            "wall time between consecutive emitted tokens", labels=("priority",))
        self._h_queue_wait = m.histogram(
            "serve_queue_wait_seconds",
            "wall time from submit to (re)admission", labels=("priority",))
        self._h_tick = m.histogram(
            "serve_tick_seconds", "wall duration of one tick() call")
        self._c_sched_tokens = m.counter(
            "serve_scheduled_tokens_total",
            "tokens packed into device steps, by phase", labels=("phase",))
        self._c_cycles = m.counter(
            "serve_modeled_cycles_total",
            "modeled tuGEMM cycles by bitwidth (serial variant)",
            labels=("bits", "bucket"))
        self._c_energy = m.counter(
            "serve_modeled_energy_joules",
            "modeled tuGEMM energy by bucket (Table-I pricing)",
            labels=("bucket",))

    def _register_gauges(self) -> None:
        """Callback gauges over structural state — read at snapshot time, no
        per-mutation pushes. Registered at the END of __init__ so every
        attribute they close over exists."""
        m = self.metrics
        m.gauge_fn("serve_active_slots",
                   lambda: sum(s is not None for s in self.slots),
                   help="slots currently holding a request")
        m.gauge_fn("serve_clock", lambda: self.clock,
                   help="logical scheduler clock (ticks since construction)")
        m.gauge_fn("serve_queue_depth",
                   lambda: {f"priority={c}": d
                            for c, d in self.admission.depths().items()},
                   help="queued requests by priority class")
        m.gauge_fn("serve_ladder_level", lambda: self.ladder.level,
                   help="degradation ladder level (0=healthy)")
        # pool occupancy gauges live on the BlockManager (cache_pages etc.,
        # registered via mgr.bind_registry at construction)

    def _note_step_energy(self, by_bits: dict, *, bucket: str) -> None:
        """Mirror one device step's pool-wide tuGEMM cycle totals into the
        registry and the modeled-energy accumulators (Table-I pricing via
        core.report.slot_energy). Powers the Perfetto energy counter track
        and serve_modeled_* metrics; no-op when the step carries no stats."""
        if not by_bits:
            return
        tick_j = 0.0
        for b, tot in by_bits.items():
            cyc = tot["serial_cycles"]
            self._c_cycles.labels(str(b), bucket).inc(cyc)
            tick_j += slot_energy(b, "serial", cyc)[1]
        self._c_energy.labels(bucket).inc(tick_j)
        self._tick_energy_j += tick_j
        self._total_energy_j += tick_j

    def _emit_counter_tracks(self, tick_wall_s: float) -> None:
        """Per-tick Perfetto counter samples (pool occupancy, queue depth,
        ladder level, modeled power). Only called when tracing is on."""
        tr = self.trace
        ts = tr.ts()
        if self.mgr is not None:
            tr.counter("pool_pages", {
                "in_use": self.mgr.pages_in_use,
                "live": self.mgr.live_pages,
            }, ts=ts)
        tr.counter("queue_depth", self.admission.depths(), ts=ts)
        tr.counter("ladder_level", {"level": self.ladder.level}, ts=ts)
        if self.track_energy:
            mw = (self._tick_energy_j / tick_wall_s * 1e3
                  if tick_wall_s > 0 else 0.0)
            tr.counter("modeled_power_mw", {"mw": round(mw, 3)}, ts=ts)
            tr.counter("modeled_energy_mj",
                       {"mj": round(self._total_energy_j * 1e3, 6)}, ts=ts)

    # ---------------------------------------------------------------- admin
    @property
    def queue(self) -> list[Request]:
        """Pop-order view of the admission queues (read-only back-compat —
        mutate through ``submit`` / the AdmissionController)."""
        return self.admission.pending_list()

    def submit(self, req: Request) -> Rejection | None:
        """Admit through the AdmissionController. Returns None when queued,
        else the structured :class:`~repro.serve.admission.Rejection` (also
        stored on ``req.rejected``). Oversized prompts still raise — that is
        a caller bug, not load."""
        if len(req.prompt) > self.capacity - 1:
            raise ValueError(
                f"request {req.rid}: prompt of {len(req.prompt)} tokens "
                f"exceeds capacity {self.capacity} - 1"
            )
        rej = self.admission.submit(req, self.clock)
        if rej is None:
            self._t_submit[req.rid] = time.perf_counter()
        if self.trace.enabled:
            tr = self.trace
            tr.name_thread(PID_REQUESTS, req.rid, f"req {req.rid}")
            if rej is None:
                self._t_queued[req.rid] = tr.ts()
                tr.instant("submit", PID_REQUESTS, req.rid, args={
                    "rid": req.rid, "tenant": req.tenant,
                    "priority": req.priority,
                    "prompt_tokens": len(req.prompt),
                })
            else:
                tr.instant("reject", PID_REQUESTS, req.rid,
                           args={"rid": req.rid, "reason": rej.reason})
        return rej

    def begin_drain(self) -> None:
        """Graceful shutdown: stop admitting new work (structured
        SHUTTING_DOWN rejections), let active slots — and preempted work
        that already ran — finish, then ``run()`` flushes whatever is still
        queued. SlotMeters survive the drain (energy_summary stays valid)."""
        self.draining = True
        self.admission.draining = True

    def _admit(self) -> None:
        for i, sl in enumerate(self.slots):
            if sl is None:
                req = self.admission.pop(self.clock, readmit_only=self.draining)
                if req is None:
                    break
                meter = None
                if self.track_energy:
                    # a preempted request resumes its existing meter: the
                    # cycles it was already charged must not reset
                    meter = self._meters_by_rid.get(req.rid)
                    if meter is None:
                        meter = SlotMeter(rid=req.rid, prompt_tokens=len(req.prompt))
                        self._meters_by_rid[req.rid] = meter
                sl = _Slot(
                    req=req,
                    prompt=list(req.prompt) + list(req.out),
                    admit_seq=self._admit_counter,
                    meter=meter,
                )
                self.slots[i] = sl
                self._admit_counter += 1
                t_sub = self._t_submit.get(req.rid)
                if t_sub is not None:
                    self._h_queue_wait.labels(req.priority).observe(
                        time.perf_counter() - t_sub)
                if self.trace.enabled:
                    tr = self.trace
                    now = tr.ts()
                    t0 = self._t_queued.pop(req.rid, now)
                    tr.complete("queued", PID_REQUESTS, req.rid, t0,
                                now - t0, args={"rid": req.rid,
                                                "priority": req.priority})
                    tr.instant("admit", PID_REQUESTS, req.rid, args={
                        "rid": req.rid, "slot": i,
                        "wait_ticks": self.clock - req.submitted_tick,
                        "readmit": req.admitted,
                    }, ts=now)
                if self.prefix_caching:
                    # longest cached block-aligned prefix of the effective
                    # prompt: fork its pages (refcount++, zero allocation)
                    # and start prefill past it — the matched tokens are
                    # never scheduled and charge zero cycles; at least one
                    # suffix token always remains to seed the first sample
                    nodes, matched = self.mgr.lookup_prefix(
                        sl.prompt, now=self.clock)
                    if matched:
                        self.mgr.fork_prefix(i, nodes, now=self.clock)
                        sl.pos = matched
                        sl.reg_blocks = len(nodes)
                        self.prefix_hits += 1
                        self.prefix_tokens_reused += matched
                        if sl.meter is not None:
                            sl.meter.cached_prompt_tokens += matched
                        if self.spec is not None:
                            # shared pages back the draft pool too (one
                            # BlockManager, same tables): whatever draft KV
                            # the original writer mirrored there is reused
                            # as-is. If it never did (it was draft-stale),
                            # drafts just propose worse — verification keeps
                            # outputs exact regardless of draft content.
                            sl.draft_pos = matched

    def _note_consumed(self, sl: _Slot) -> None:
        """High-water-mark the original prompt tokens committed to KV —
        read by admission.settle at every terminal/requeue transition."""
        sl.req.prompt_consumed = max(
            sl.req.prompt_consumed, min(sl.pos, len(sl.req.prompt)))

    def _finish(self, i: int) -> None:
        sl = self.slots[i]
        sl.req.done = True
        if sl.req.deadline is not None and self.clock > sl.req.deadline:
            self.deadline_misses += 1
        self.finished.append(sl.req)
        self.final_kv_lens[sl.req.rid] = sl.pos
        # index the finished sequence's full blocks before releasing: its
        # pages outlive the slot as cached prefixes (refcount 0, evictable)
        self._register_prefix(i)
        self._note_consumed(sl)
        # satellite fix: refund the unused max_new - generated remainder —
        # tenants that stop early at EOS no longer burn phantom budget
        self.admission.settle(sl.req)
        if sl.meter is not None:
            self.finished_meters.append(sl.meter)
            self._meters_by_rid.pop(sl.req.rid, None)
        if self.mgr is not None:
            self.mgr.release(i)
        self.slots[i] = None
        self._t_submit.pop(sl.req.rid, None)
        self._t_emit.pop(sl.req.rid, None)
        if self.trace.enabled:
            self.trace.instant("finish", PID_REQUESTS, sl.req.rid, args={
                "rid": sl.req.rid, "generated": len(sl.req.out),
                "deadline_missed": bool(
                    sl.req.deadline is not None
                    and self.clock > sl.req.deadline),
            })

    def _shed_slot(self, i: int, reason: str, detail: str = "") -> None:
        """Terminate an *active* slot with a structured rejection (e.g. a
        numerical fault with no fallback path). Pages are released; the
        request is terminal — rejected, never silently dropped."""
        sl = self.slots[i]
        r = Rejection(rid=sl.req.rid, reason=reason, detail=detail,
                      tick=self.clock)
        sl.req.rejected = r
        self.admission.rejections.append(r)
        self.admission.sheds += 1
        # settle net of what actually ran: prompt tokens committed plus
        # tokens generated stay charged, only the remainder refunds
        self._note_consumed(sl)
        self.admission.settle(sl.req)
        if sl.meter is not None:
            self.finished_meters.append(sl.meter)
            self._meters_by_rid.pop(sl.req.rid, None)
        if self.mgr is not None:
            self.mgr.release(i)
        self.slots[i] = None
        self._t_submit.pop(sl.req.rid, None)
        self._t_emit.pop(sl.req.rid, None)
        if self.trace.enabled:
            self.trace.instant("shed", PID_REQUESTS, sl.req.rid, args={
                "rid": sl.req.rid, "reason": reason})

    def _preempt_one(self) -> bool:
        """Recompute-preemption under pool pressure (ladder level 3):
        release the lowest-priority-youngest slot's pages and requeue it at
        the front of its class; its effective prompt (original + generated
        so far) is re-prefilled on readmission. Never preempts the last
        active slot (it must be able to drain)."""
        cand = [i for i, s in enumerate(self.slots) if s is not None]
        if len(cand) <= 1:
            return False
        i = max(cand, key=lambda j: (PRIORITY_RANK[self.slots[j].req.priority],
                                     self.slots[j].admit_seq))
        sl = self.slots[i]
        # the victim's consumption must be current *before* it re-enters the
        # queue: if it expires there, the shed settles against these numbers
        # (satellite fix: the old full-cost refund ignored consumed work)
        self._note_consumed(sl)
        # its committed blocks are still perfectly good KV — index them so
        # the readmission (and anyone sharing the prompt) forks instead of
        # re-prefilling from scratch
        self._register_prefix(i)
        if self.mgr is not None:
            self.mgr.release(i)
        self.admission.requeue_front(sl.req)
        self.slots[i] = None
        self.preemptions += 1
        self.ladder.escalate_to(self.clock, 3, "preemption")
        if self.trace.enabled:
            self.trace.instant("preempt", PID_REQUESTS, sl.req.rid, args={
                "rid": sl.req.rid, "slot": i, "pos": sl.pos})
            self._t_queued[sl.req.rid] = self.trace.ts()
        return True

    # ---------------------------------------------------------- fault hooks
    def _alloc_fault_hook(self, slot: int, new_len: int) -> bool:
        """BlockManager hook: injected page-allocation failure for
        (clock, slot) pairs named by the fault plan."""
        if self.faults.fires(self.clock, "alloc_fail", slot):
            self._fault_fired = True
            return True
        return False

    def _apply_tick_faults(self) -> None:
        """Tick-start faults: forced preemption storms and draft staleness.
        (alloc_fail fires inside BlockManager.extend; nan_logits after the
        step.)"""
        for ev in self.faults.at(self.clock, "preempt_storm"):
            for _ in range(ev.arg):
                if not self._preempt_one():
                    break
        for ev in self.faults.at(self.clock, "draft_stale"):
            sl = self.slots[ev.arg % self.max_batch]
            if sl is not None and self.spec is not None and not sl.draft_stale:
                sl.draft_stale = True
                sl.draft_gap = []
                self.draft_stale_events += 1

    def _note_stall(self, stalled: int) -> None:
        """Satellite fix: pool-exhaustion row stalls used to skip the tick
        silently. Count them, escalate the ladder, and log once per
        pressure episode (not once per tick — a long episode is one event)."""
        self.stalled_rows_total += stalled
        self._stall_this_tick = True
        self.ladder.note_pressure(self.clock, "alloc_stall", ceil=3)
        if not self._in_stall:
            self.stall_episodes += 1
            self._in_stall = True
            pool = (f"{self.mgr.pages_in_use}/{self.mgr.num_pages}"
                    if self.mgr is not None else "dense")
            log.warning(kv(
                "stall", tick=self.clock, rows=stalled, pool=pool,
                ladder=self.ladder.snapshot()["name"],
                episode=self.stall_episodes,
            ))
            if self.trace.enabled:
                self.trace.instant("stall", PID_SCHED, TID_TICK, args={
                    "tick": self.clock, "rows": stalled})

    # ---------------------------------------------------------- prefix cache
    def _register_prefix(self, i: int) -> None:
        """Index slot ``i``'s newly-committed full blocks in the prefix trie
        (DESIGN.md §11). Called after every commit point and before any
        release, so a concurrent request sharing the prompt can fork pages
        the moment their block fills — not only after the writer finishes.
        O(1) when no new block completed."""
        if not self.prefix_caching:
            return
        sl = self.slots[i]
        if sl is None:
            return
        bs = self.rc.block_size
        if sl.pos // bs <= sl.reg_blocks:
            return
        seq = list(sl.req.prompt) + list(sl.req.out)
        self.mgr.register_prefix(i, seq[: sl.pos], now=self.clock)
        sl.reg_blocks = sl.pos // bs

    def _drain_cow(self) -> None:
        """Perform the device page copies owed by copy-on-write resolutions
        queued since the last step: one jitted ``pool[:, dst] = pool[:, src]``
        tree-map per copy, applied to the target caches AND the draft pool
        (both index pages by the same block tables, so a retabled page must
        exist in both). src/dst are traced scalars — one compile per cache
        tree structure for the engine's lifetime. Must run before the step
        that writes into a COW'd destination page."""
        if self.mgr is None:
            return
        copies = self.mgr.drain_cow_copies()
        if not copies:
            return
        if self._cow_jit is None:
            self._cow_jit = jax.jit(
                lambda caches, src, dst: jax.tree.map(
                    lambda x: x.at[:, dst].set(x[:, src]), caches),
                donate_argnums=(0,),
            )
        for s, d in copies:
            s, d = jnp.int32(s), jnp.int32(d)
            self.caches = self._cow_jit(self.caches, s, d)
            if self.spec is not None:
                self.spec.caches = self._cow_jit(self.spec.caches, s, d)

    # ----------------------------------------------------------------- tick
    def _plan(self):
        """Fill one tick's rows under the token budget: decode rows first
        (a burst of admissions must never stall decodes), then prompt
        chunks FIFO. Rows whose page allocation fails stall this tick —
        counted and reported (``stalled``), never silent. Slots are scanned
        in a per-tick rotated order so a budget tighter than the active row
        count round-robins instead of starving the high-index rows. Under
        pressure (ladder level >= 2) the prefill portion of the budget
        shrinks — decode rows, which release pages soonest, keep priority."""
        rows, W = self.max_batch, self.chunk
        tokens = np.zeros((rows, W), np.int32)
        pos = np.zeros(rows, np.int32)
        lens = np.zeros(rows, np.int32)
        budget = self.token_budget
        stalled = 0
        decode_rows: list[int] = []
        prefill_rows: list[int] = []
        order = [(self._rr + k) % rows for k in range(rows)]
        for i in order:
            sl = self.slots[i]
            if sl is None:
                continue
            pos[i] = sl.pos
            if not sl.prefilling and budget > 0:
                if self.mgr is not None and not self.mgr.extend(i, sl.pos + 1):
                    stalled += 1  # pool exhausted — row stalls this tick
                    continue
                tokens[i, 0] = sl.last_token
                lens[i] = 1
                budget -= 1
                decode_rows.append(i)
        pbudget = min(budget, self.ladder.prefill_budget(self.token_budget, W))
        for i in order:
            sl = self.slots[i]
            if sl is None or lens[i] or not sl.prefilling or pbudget <= 0:
                continue
            n = min(W, len(sl.prompt) - sl.pos, pbudget)
            if self.mgr is not None and not self.mgr.extend(i, sl.pos + n):
                stalled += 1
                continue
            tokens[i, :n] = sl.prompt[sl.pos : sl.pos + n]
            lens[i] = n
            pbudget -= n
            prefill_rows.append(i)
        return tokens, pos, lens, decode_rows, prefill_rows, stalled

    def _tables(self):
        """Device copy of the block tables, re-uploaded only when the host
        manager mutated since the last tick (version-keyed)."""
        if self.mgr is None:
            return None
        if self._tables_version != self.mgr.version:
            self._tables_dev = jnp.asarray(self.mgr.tables)
            self._tables_version = self.mgr.version
        return self._tables_dev

    def _sample_keys(self, pos, lens):
        """Per-row fold_in(seed, rid, position) keys for this tick's draws —
        position is the absolute sequence index each row samples, so the
        stream never depends on how ticks were packed. Greedy ticks skip the
        fold entirely (sample() ignores the key at temperature 0)."""
        if self.temperature <= 0.0:
            return self.key
        rids = [sl.req.rid if (sl := self.slots[i]) is not None else 0
                for i in range(self.max_batch)]
        posn = [int(pos[i]) + int(lens[i]) for i in range(self.max_batch)]
        return request_keys(self.key, rids, posn)

    def _emit(self, i: int, token: int) -> None:
        """Append one sampled/accepted token to slot ``i``'s request.

        A request's very first token rides its prefill (legacy semantics:
        not a decode token); any later one — including the sample after a
        preemption's re-prefill — is a decode token, so meter['tokens'] is
        preemption-invariant."""
        sl = self.slots[i]
        continuing = bool(sl.req.out)
        sl.req.out.append(token)
        sl.last_token = token
        self.generated_tokens += 1
        if sl.meter is not None:
            sl.meter.emitted_tokens += 1
            if continuing:
                sl.meter.decode_tokens += 1
        # latency accounting (wall clock, rid-keyed so it survives
        # preemption — the requeue gap is real user-visible latency)
        now = time.perf_counter()
        rid = sl.req.rid
        prev = self._t_emit.get(rid)
        if prev is not None:
            self._h_itl.labels(sl.req.priority).observe(now - prev)
        elif rid in self._t_submit:
            self._h_ttft.labels(sl.req.priority).observe(
                now - self._t_submit[rid])
        self._t_emit[rid] = now

    def _end_tick(self, ran: bool) -> bool:
        """Per-tick ladder/admission bookkeeping: relax toward healthy on
        clean ticks (the ladder ignores the call if pressure was noted this
        clock), close stall episodes, and (un)pause admissions at level 5."""
        if not self._stall_this_tick:
            self._in_stall = False
        self.ladder.note_clean(self.clock)
        self.admission.paused = self.ladder.level >= len(LADDER_LEVELS) - 1
        self.ladder.tick()
        return ran

    def tick(self) -> bool:
        """Plan + run one mixed step. Returns False when nothing ran.

        Advances the logical ``clock`` unconditionally — deadlines, fault
        plans, and the ladder key on it, so even idle ticks count as time.

        Observability wrapper: one ``tick`` span (phase spans nest inside
        ``_tick_inner``), per-tick counter tracks, and the tick-duration
        histogram. The disabled-tracer path adds one branch + one
        ``perf_counter`` pair over the pre-§14 code."""
        t0 = time.perf_counter()
        tr = self.trace
        if tr.enabled:
            self._tick_energy_j = 0.0
            with tr.span("tick", args={"clock": self.clock + 1}):
                ran = self._tick_inner()
            wall = time.perf_counter() - t0
            self._emit_counter_tracks(wall)
        else:
            ran = self._tick_inner()
            wall = time.perf_counter() - t0
        self._h_tick.observe(wall)
        return ran

    def _tick_inner(self) -> bool:
        self.clock += 1
        self._fault_fired = False
        self._stall_this_tick = False
        tr = self.trace
        with tr.span("admit"):
            if self.faults is not None:
                self._apply_tick_faults()
            if self.admission.queue_pressure():
                # a bounded queue at its limit is the signal that can push the
                # ladder past preempt into shed/reject
                self.ladder.note_pressure(self.clock, "queue_full")
            if self.ladder.level >= 4:
                # ladder level 4: shed queued work that cannot or should not run
                # — expired requests and the whole batch class
                self.admission.shed_expired(self.clock)
                self.admission.shed_class("batch", self.clock)
            self._admit()
        with tr.span("plan") as plan_span:
            tokens, pos, lens, decode_rows, prefill_rows, stalled = self._plan()
            if stalled:
                self._note_stall(stalled)
            # pool pressure: nothing schedulable while slots are active means
            # every row's page allocation failed — recompute-preempt until one
            # can proceed (bounded by max_batch-1 preemptions)
            while not (decode_rows or prefill_rows) and self._preempt_one():
                tokens, pos, lens, decode_rows, prefill_rows, stalled = self._plan()
                if stalled:
                    self._note_stall(stalled)
            scheduled = decode_rows + prefill_rows
            if tr.enabled:
                plan_span.args = {"decode_rows": len(decode_rows),
                                  "prefill_rows": len(prefill_rows),
                                  "stalled": stalled}
        if not scheduled:
            if any(s is not None for s in self.slots):
                if self._fault_fired:
                    # injected exhaustion on every schedulable row: idle the
                    # tick — the fault is keyed to this clock and passes
                    self.idle_fault_ticks += 1
                    return self._end_tick(True)
                self.engine_stalls += 1
                raise RuntimeError(
                    "page pool cannot back a single active sequence "
                    f"({self.mgr.num_pages if self.mgr else 0} pages of "
                    f"{self.rc.block_size} tokens)"
                )
            return self._end_tick(False)
        if self.spec is not None:
            return self._end_tick(
                self._spec_tick(tokens, pos, lens, decode_rows, prefill_rows))
        with tr.span("cow_drain"):
            self._drain_cow()
        with tr.span("tables"):
            tables = self._tables()

        # width-adaptive tick: decode-only ticks run the step at width 1
        # (decode rows only occupy column 0) instead of paying the full
        # chunk width in padded query compute — a second jit cache entry,
        # still O(1) compiles for the engine's lifetime
        width = self.chunk if prefill_rows else 1

        # quarantined rows run through the fallback-policy step instead of
        # the (suspect) target-policy step; everything else is unchanged
        fbset = {i for i in scheduled if self.slots[i].fallback}
        fb_np = None
        if fbset:
            with tr.span("fallback_step"):
                fb_np = self._run_fallback(tokens, pos, lens, tables,
                                           sorted(fbset), width)
            if fb_np is None:
                for i in sorted(fbset):
                    self._shed_slot(i, RejectReason.NUMERICAL_FAULT,
                                    "non-finite logits and no fallback step")
                decode_rows = [i for i in decode_rows if i not in fbset]
                prefill_rows = [i for i in prefill_rows if i not in fbset]
                scheduled = decode_rows + prefill_rows
                fbset = set()
                if not scheduled:
                    return self._end_tick(True)
        main_rows = [i for i in scheduled if i not in fbset]
        # writable host copy: fault injection + row merging mutate it
        logits_np = None if fb_np is None else fb_np.copy()
        # device_step ends at the host logits materialization (the sync)
        with tr.span("device_step", args={
                "rows": len(main_rows), "width": width,
                "tokens": int(sum(int(lens[i]) for i in scheduled)),
                **self._page_steps(pos, lens)}
                if tr.enabled else None) as step_span:
            if main_rows:
                main_np, step_by_bits = self._main_step(
                    tokens, pos, lens, tables, fbset, width)
                if logits_np is None:
                    logits_np = main_np
                else:
                    for i in main_rows:
                        logits_np[i] = main_np[i]
            else:
                step_by_bits = {}
            if self.logits_hook is not None:
                self.logits_hook([self.slots[i].req.rid for i in scheduled],
                                 logits_np[scheduled])
            self.ticks += 1
            n_prefill = sum(int(lens[i]) for i in prefill_rows)
            self.prefill_tokens_computed += n_prefill
            if n_prefill:
                self._c_sched_tokens.labels("prefill").inc(n_prefill)
            if decode_rows:
                self._c_sched_tokens.labels("decode").inc(len(decode_rows))
            if self.track_energy:
                self._note_step_energy(step_by_bits, bucket="target")
        if tr.enabled:
            for i in scheduled:
                sl = self.slots[i]
                if sl is None:
                    continue
                tr.complete(
                    "prefill" if i in prefill_rows else "decode",
                    PID_REQUESTS, sl.req.rid, step_span.ts, step_span.dur,
                    args={"rid": sl.req.rid, "pos": int(pos[i]),
                          "tokens": int(lens[i]),
                          **({"path": "fallback"} if i in fbset else {})})
        with tr.span("commit"):
            self._commit(logits_np, scheduled, main_rows, fbset, pos, lens,
                         step_by_bits)
        return self._end_tick(True)

    def _page_steps(self, pos, lens) -> dict:
        """The paged kernel's grid steps in one call of this tick's step
        (each layer makes one): ``pages_grid`` = rows × table width, and
        ``pages_live`` = the steps below a row's live length ``pos + lens``,
        the only ones that fetch or compute (kernels/flash_paged.py). Every
        row counts, as in the kernel: a slot that waits this tick keeps its
        ``pos``, so its pages are still attended. Empty without a block
        table."""
        if self.mgr is None:
            return {}
        live = -(-(pos.astype(np.int64) + lens) // self.rc.block_size)
        return {"pages_live": int(live.sum()),
                "pages_grid": self.max_batch * self.mgr.max_blocks}

    def _main_step(self, tokens, pos, lens, tables, fbset, width):
        """The target-policy step over every scheduled row outside ``fbset``.
        Returns its last-column logits as a writable (B, V) f32 host array
        and the step's cycle totals by bitwidth.

        Sub-spans of ``device_step``: ``step_inputs`` (the uploads),
        ``step_launch`` (dispatch), ``step_wait`` (the device, waited for
        only while tracing), ``logits_fetch`` (device to host) and
        ``logits_widen`` (the host f32 copy)."""
        tr = self.trace
        step_by_bits: dict = {}
        with tr.span("step_inputs"):
            lens_main = lens.copy()
            for i in fbset:
                lens_main[i] = 0
            tokens_d = jnp.asarray(tokens[:, :width])
            pos_d = jnp.asarray(pos)
            lens_d = jnp.asarray(lens_main)
        with tr.span("step_launch"):
            out = self._step(self.params, self.caches, tokens_d, pos_d,
                             lens_d, tables)
        if self.mesh is not None:
            # sharded step always returns the 3-tuple: the raw stats
            # tree carries per-device leading (dp, tp) axes plus the MoE
            # drop counters even when energy tracking is off
            self.caches, logits, raw = out
            raw_np = jax.tree.map(np.asarray, raw)
            self.moe_dropped_tokens += self._mesh_step.moe_drops(raw_np)
            self._accum_comms(self._mesh_step.comms_for(width))
            if self.track_energy:
                tree = self._mesh_step.merge_stats(raw_np)
                step_by_bits = tree_totals_by_bits(tree)
                self._accum_device_load(
                    self._mesh_step.device_serial_by_bits(raw_np))
        elif self.track_energy:
            self.caches, logits, tree = out
            step_by_bits = tree_totals_by_bits(tree)
        else:
            self.caches, logits = out
        for b, d in step_by_bits.items():
            acc = self.cycles_by_bits.setdefault(
                b, {"serial_cycles": 0, "parallel_cycles": 0})
            for k2, v2 in d.items():
                acc[k2] += int(v2)
        if tr.enabled:
            with tr.span("step_wait"):
                logits.block_until_ready()
        with tr.span("logits_fetch"):
            host = np.asarray(logits)
        with tr.span("logits_widen"):
            return np.array(host, np.float32), step_by_bits   # writable copy

    def _commit(self, logits_np, scheduled, main_rows, fbset, pos, lens,
                step_by_bits) -> None:
        """Guard, sample and emit one plain tick's rows, under the sub-spans
        ``logits_check`` (fault injection and the ``isfinite`` scan),
        ``sample`` (upload, sampling, the tokens back to the host) and
        ``emit`` (per-row bookkeeping, finish, prefix registration)."""
        tr = self.trace
        with tr.span("logits_check"):
            # induced numerical faults corrupt target-policy rows only (the
            # fallback step models the numerically-safe path)
            if self.faults is not None:
                for ev in self.faults.at(self.clock, "nan_logits"):
                    r = ev.arg % self.max_batch
                    if r in main_rows:
                        logits_np[r] = np.nan
            bad = [i for i in scheduled if not np.isfinite(logits_np[i]).all()]
            for i in bad:
                if self.slots[i].fallback:
                    # the numerically-safe path itself is non-finite: terminal
                    self._shed_slot(i, RejectReason.NUMERICAL_FAULT,
                                    "non-finite logits at the fallback policy")
                else:
                    self._quarantine(i)
            badset = set(bad)

        with tr.span("sample"):
            toks = np.asarray(sample(self._sample_keys(pos, lens),
                                     jnp.asarray(logits_np), self.temperature))

        with tr.span("emit"):
            total = float(sum(int(lens[i]) for i in main_rows)) or 1.0
            for i in scheduled:
                sl = self.slots[i]
                if sl is None:
                    continue  # shed this tick (terminal numerical fault)
                if (self.track_energy and sl.meter is not None
                        and i not in fbset):
                    # quarantined rows stay charged: wasted compute is real
                    sl.meter.add_share(step_by_bits, int(lens[i]) / total)
                if i in badset:
                    continue  # quarantined: same position retries next tick
                was_decoding = not sl.prefilling
                sl.pos += int(lens[i])
                sl.retries = 0
                if was_decoding or not sl.prefilling:
                    # decode rows and just-completed prefills both sampled a token
                    self._emit(i, int(toks[i]))
                    if len(sl.req.out) >= sl.req.max_new or sl.pos >= self.capacity - 1:
                        self._finish(i)
                        continue
                self._register_prefix(i)
            self._rr = (self._rr + 1) % self.max_batch

    # ------------------------------------------------------ numerical guard
    def _quarantine(self, i: int) -> None:
        """Non-finite logits on row ``i``: roll the row back to its pre-tick
        state (pages freed via truncate, position unchanged, nothing
        emitted) and retry next tick. The first ``nan_retry_limit`` retries
        re-run the same policy — a *transient* fault clears bit-exactly; a
        persistent one escalates to the ``rc.fallback_policy`` step
        (sticky). Ties robustness back to quantization risk: overflow at
        int2/int4 is exactly the fault this guard exists for."""
        sl = self.slots[i]
        self.nan_events += 1
        if self.mgr is not None:
            self.mgr.truncate(i, sl.pos)
        if self.spec is not None:
            # speculative state past the committed prefix is suspect too
            sl.draft_pos = min(sl.draft_pos, sl.pos)
            sl.draft_gap = []
            if not sl.draft_stale:
                sl.draft_stale = True
                self.draft_stale_events += 1
        sl.retries += 1
        if sl.retries > self.nan_retry_limit and not sl.fallback:
            sl.fallback = True
            self.fallback_retries += 1
        log.warning(kv(
            "nan_logits", rid=sl.req.rid, tick=self.clock, row=i,
            retries=sl.retries,
            action="fallback" if sl.fallback else "retry",
        ))
        if self.trace.enabled:
            self.trace.instant("nan_quarantine", PID_REQUESTS, sl.req.rid,
                               args={"rid": sl.req.rid, "row": i})

    def _run_fallback(self, tokens, pos, lens, tables, fb_rows, width):
        """One mixed step at ``rc.fallback_policy`` (default ``*=bf16``) for
        the quarantined rows only (other rows masked to length 0). Returns
        last-column logits (B, V), or None when the fallback path is
        unusable (e.g. prequant-packed params cannot re-lower at another
        policy) — callers then shed with a structured NUMERICAL_FAULT."""
        if self._fb_unavailable:
            return None
        try:
            if self._fb_step is None:
                import dataclasses as _dc

                rc_fb = _dc.replace(
                    self.rc,
                    quant_policy=self.rc.fallback_policy or "*=bf16",
                    gemm_backend="bf16", gemm_mode="dynamic", quant_layers=(),
                    spec_gamma=0, draft_policy=None,
                )
                # no donation: a failing first call must not invalidate caches
                if self.mesh is not None:
                    from ..parallel import serve_mesh as _sm

                    self._fb_handle = _sm.build_sharded_step(
                        self.cfg, rc_fb, self.mesh, self.params, self.caches,
                        with_stats=False, donate=False,
                    )
                    self._fb_step = lambda *a: self._fb_handle(*a)[:2]
                else:
                    self._fb_step = jax.jit(build_mixed_step(self.cfg, rc_fb))
            lens_fb = np.zeros_like(lens)
            for i in fb_rows:
                lens_fb[i] = lens[i]
            self.caches, logits = self._fb_step(
                self.params, self.caches,
                jnp.asarray(tokens[:, :width]), jnp.asarray(pos),
                jnp.asarray(lens_fb), tables,
            )
        except Exception as e:  # noqa: BLE001 — any lowering failure is terminal
            log.error(kv("fallback_unavailable", tick=self.clock,
                         policy=self.rc.fallback_policy or "*=bf16",
                         error=repr(e)))
            self._fb_unavailable = True
            return None
        return np.asarray(logits, np.float32)

    # ------------------------------------------------------------ spec tick
    def _spec_tick(self, tokens, pos, lens, decode_rows, prefill_rows) -> bool:
        """One speculative tick (DESIGN.md §9).

        Decode rows draft up to γ candidates against the int-low draft view
        + draft KV pool (serve.spec), then ONE chunked-prefill-shaped target
        step verifies all γ+1 positions per decode row while also running
        the tick's ordinary prefill chunks; rejected candidates are rolled
        back via BlockManager.truncate so they never leak KV. Prefill chunks
        are mirrored into the draft pool (at the draft policy's near-free
        bitwidth) so a slot can start drafting the moment it finishes
        prefilling."""
        from .spec import DraftRow, greedy_accept, rejection_accept

        spec, rows = self.spec, self.max_batch
        tr = self.trace
        W = tokens.shape[1]

        # ---- stale-draft resync (one slot/tick, healthy ladder only): re-
        # ingest the committed suffix the draft pool is missing, one chunk
        # window per tick, so a stale slot recovers drafting instead of
        # falling back to plain decode forever. Under pressure the pass is
        # skipped — a stale draft costs speedup, not correctness.
        if self.ladder.level == 0:
            for i, sl in enumerate(self.slots):
                if (sl is None or sl.prefilling or sl.fallback
                        or not sl.draft_stale):
                    continue
                behind = sl.pos - sl.draft_pos
                if behind > 0:
                    seq = list(sl.req.prompt) + list(sl.req.out)
                    n = min(self.chunk, behind)
                    rt = np.zeros((rows, self.chunk), np.int32)
                    rp = np.zeros(rows, np.int32)
                    rl = np.zeros(rows, np.int32)
                    rt[i, :n] = seq[sl.draft_pos : sl.draft_pos + n]
                    rp[i] = sl.draft_pos
                    rl[i] = n
                    by_bits = spec.mirror_prefill(
                        jnp.asarray(rt), jnp.asarray(rp), jnp.asarray(rl),
                        self._tables(),
                    )
                    if by_bits and sl.meter is not None:
                        sl.meter.add_share(by_bits, 1.0, bucket="draft")
                    sl.draft_pos += n
                if sl.draft_pos >= sl.pos:
                    sl.draft_stale = False
                    sl.draft_gap = []
                    self.draft_resyncs += 1
                break

        # per-row candidate budget: never draft past max_new or capacity,
        # cap γ at the ladder's current level (degrade-spec-γ is rung 1),
        # and degrade γ (not stall) when the page pool cannot back the
        # optimistic γ+1 verify writes
        gcap = self.ladder.gamma_cap(spec.gamma)
        g: dict[int, int] = {}
        draft_rows: list[DraftRow] = []
        for i in decode_rows:
            sl = self.slots[i]
            remaining = sl.req.max_new - len(sl.req.out)
            gi = max(0, min(gcap, remaining - 1, self.capacity - 2 - sl.pos))
            if sl.draft_stale or sl.fallback:
                gi = 0
            while gi > 0 and self.mgr is not None and not self.mgr.extend(i, sl.pos + gi + 1):
                gi -= 1
            g[i] = gi
            if gi > 0:
                draft_rows.append(DraftRow(
                    row=i, rid=sl.req.rid, pos=sl.pos, draft_pos=sl.draft_pos,
                    gap=list(sl.draft_gap), last_token=sl.last_token, g=gi,
                ))
        # resolve copy-on-write before anything (draft or verify) writes
        # into this tick's pages — covers _plan's and the γ-extends above
        with tr.span("cow_drain"):
            self._drain_cow()
        tables = self._tables()

        # quarantined rows run the fallback-policy step instead (masked out
        # of draft + verify below); unavailable fallback sheds them
        fbset = {i for i in decode_rows + prefill_rows if self.slots[i].fallback}
        fb_np = None
        if fbset:
            fbw = W if any(i in fbset for i in prefill_rows) else 1
            with tr.span("fallback_step"):
                fb_np = self._run_fallback(tokens, pos, lens, tables,
                                           sorted(fbset), fbw)
            if fb_np is None:
                for i in sorted(fbset):
                    self._shed_slot(i, RejectReason.NUMERICAL_FAULT,
                                    "non-finite logits and no fallback step")
                decode_rows = [i for i in decode_rows if i not in fbset]
                prefill_rows = [i for i in prefill_rows if i not in fbset]
                fbset = set()
                if not (decode_rows or prefill_rows):
                    return True

        # ---- draft phase: γ sequential low-bit steps over the draft rows
        proposals: dict[int, list[int]] = {}
        qlogits: list[np.ndarray] = []
        if draft_rows:
            _dt = tr.ts()
            proposals, qlogits, draft_events = spec.draft(
                draft_rows, tables, self.temperature, self.key
            )
            for by_bits, weights in draft_events:
                for i, w in weights.items():
                    sl = self.slots[i]
                    if sl is not None and sl.meter is not None:
                        sl.meter.add_share(by_bits, w, bucket="draft")
                if self.track_energy:
                    self._note_step_energy(by_bits, bucket="draft")
            n_drafted = 0
            for r in draft_rows:
                sl = self.slots[r.row]
                # the draft ingested [gap..., last, d_1..d_{g-1}] — its pool
                # now covers sequence positions < pos + g
                sl.draft_pos = r.pos + r.g
                sl.draft_gap = []
                self.drafted_tokens += r.g
                n_drafted += r.g
                if sl.meter is not None:
                    sl.meter.drafted_tokens += r.g
            self._c_sched_tokens.labels("draft").inc(n_drafted)
            if tr.enabled:
                _ddur = tr.ts() - _dt
                tr.complete("draft", PID_SCHED, TID_TICK, _dt, _ddur, args={
                    "rows": len(draft_rows), "drafted": n_drafted})
                for r in draft_rows:
                    tr.complete("draft", PID_REQUESTS, r.rid, _dt, _ddur,
                                args={"rid": r.rid, "pos": r.pos,
                                      "gamma": r.g})

        # ---- verify + prefill: one target step, every column's logits kept
        Wv = max(spec.gamma + 1, W if prefill_rows else 0)
        vt = np.zeros((rows, Wv), np.int32)
        vlens = np.zeros(rows, np.int32)
        for i in prefill_rows:
            if i in fbset:
                continue          # runs through the fallback step instead
            vt[i, : int(lens[i])] = tokens[i, : int(lens[i])]
            vlens[i] = lens[i]
        for i in decode_rows:
            if i in fbset:
                continue
            sl = self.slots[i]
            vt[i, 0] = sl.last_token
            for j, t in enumerate(proposals.get(i, [])):
                vt[i, 1 + j] = t
            vlens[i] = g[i] + 1
        _st = tr.ts()
        out = self._vstep(
            self.params, self.caches,
            jnp.asarray(vt), jnp.asarray(pos), jnp.asarray(vlens), tables,
        )
        if self.track_energy:
            self.caches, logits, tree = out
            step_by_bits = tree_totals_by_bits(tree)
            self._note_step_energy(step_by_bits, bucket="target")
        else:
            self.caches, logits = out
        self.ticks += 1
        n_prefill = sum(int(lens[i]) for i in prefill_rows)
        self.prefill_tokens_computed += n_prefill
        if n_prefill:
            self._c_sched_tokens.labels("prefill").inc(n_prefill)
        if decode_rows:
            self._c_sched_tokens.labels("decode").inc(len(decode_rows))
        scheduled = decode_rows + prefill_rows
        total = float(sum(int(vlens[i]) for i in scheduled)) or 1.0
        if self.track_energy:
            for i in scheduled:
                sl = self.slots[i]
                if sl.meter is not None and i not in fbset:
                    sl.meter.add_share(step_by_bits, int(vlens[i]) / total)

        # ---- mirror prefill chunks into the draft KV pool
        main_prefill = [i for i in prefill_rows if i not in fbset]
        if main_prefill:
            mlens = lens.copy()
            for i in decode_rows:
                mlens[i] = 0
            for i in fbset:
                mlens[i] = 0      # fallback rows' drafts are stale anyway
            with tr.span("mirror"):
                m_by_bits = spec.mirror_prefill(
                    jnp.asarray(tokens[:, :W]), jnp.asarray(pos),
                    jnp.asarray(mlens), tables,
                )
            if m_by_bits and self.track_energy:
                self._note_step_energy(m_by_bits, bucket="draft")
            m_total = float(sum(int(mlens[i]) for i in main_prefill)) or 1.0
            for i in main_prefill:
                sl = self.slots[i]
                if m_by_bits and sl.meter is not None:
                    sl.meter.add_share(m_by_bits, int(mlens[i]) / m_total,
                                       bucket="draft")
                sl.draft_pos = int(pos[i]) + int(lens[i])

        # ---- numerical-fault guard (injection, then detection)
        logits_np = np.array(logits, np.float32)             # (B, Wv, V) copy
        if tr.enabled:
            # ends at the host materialization above (the device sync); the
            # interval includes the mirror dispatch, which is async
            _sdur = tr.ts() - _st
            tr.complete("device_step", PID_SCHED, TID_TICK, _st, _sdur, args={
                "rows": len(scheduled), "width": int(Wv), "kind": "verify",
                **self._page_steps(pos, vlens)})
            for i in scheduled:
                sl = self.slots[i]
                if sl is None:
                    continue
                tr.complete(
                    "prefill" if i in prefill_rows else "verify",
                    PID_REQUESTS, sl.req.rid, _st, _sdur,
                    args={"rid": sl.req.rid, "pos": int(pos[i]),
                          "tokens": int(vlens[i]),
                          **({"path": "fallback"} if i in fbset else {})})
        _ct = tr.ts()
        if self.faults is not None:
            for ev in self.faults.at(self.clock, "nan_logits"):
                r = ev.arg % rows
                if r in scheduled and r not in fbset:
                    logits_np[r] = np.nan
        bad = []
        for i in scheduled:
            cols = fb_np[i] if i in fbset else logits_np[i, : max(int(vlens[i]), 1)]
            if not np.isfinite(cols).all():
                bad.append(i)
        for i in bad:
            if i in fbset:
                # the numerically-safe path itself is non-finite: terminal
                self._shed_slot(i, RejectReason.NUMERICAL_FAULT,
                                "non-finite logits at the fallback policy")
            else:
                self._quarantine(i)
        badset = set(bad)
        decode_rows = [i for i in decode_rows if i not in badset]
        prefill_rows = [i for i in prefill_rows if i not in badset]
        fbset -= badset

        # ---- acceptance + emission
        if self.temperature <= 0.0:
            argmax = np.argmax(logits_np, axis=-1)           # (B, Wv)
        for i in decode_rows:
            if i in fbset:
                continue          # emitted from the fallback logits below
            sl = self.slots[i]
            if self.temperature <= 0.0:
                n_acc, emitted = greedy_accept(proposals.get(i, []), argmax[i])
            else:
                q_rows = np.stack([qlogits[j][i] for j in range(g[i])]) \
                    if g[i] else np.zeros((0, logits_np.shape[-1]), np.float32)
                n_acc, emitted = rejection_accept(
                    self.key, sl.req.rid, sl.pos, proposals.get(i, []),
                    logits_np[i, : g[i] + 1], q_rows, self.temperature,
                )
            self.accepted_draft_tokens += n_acc
            if sl.meter is not None:
                sl.meter.accepted_draft_tokens += n_acc
            # rollback: keep only the accepted prefix's KV in both pools
            new_len = sl.pos + n_acc + 1
            if self.mgr is not None:
                self.mgr.truncate(i, new_len)
            sl.pos = new_len
            sl.retries = 0
            if g[i] == 0:
                # plain-decode fallback tick: the draft never saw the old
                # last token — queue it for the next catch-up step
                if not sl.draft_stale:
                    sl.draft_gap.append(sl.last_token)
                    if len(sl.draft_gap) > spec.gamma:
                        sl.draft_stale = True
                        sl.draft_gap = []
            elif sl.draft_pos >= new_len:
                # a candidate was rejected: the draft KV past the accepted
                # prefix is dead too (position new_len-1, whose input is the
                # last accepted token, stays valid)
                sl.draft_pos = new_len
            else:
                # all γ accepted: the draft never ingested d_γ — carry it as
                # catch-up for the next tick's first draft step
                sl.draft_gap = [int(emitted[-2])]
            for t in emitted:
                self._emit(i, int(t))
            if len(sl.req.out) >= sl.req.max_new or sl.pos >= self.capacity - 1:
                self._finish(i)
            else:
                self._register_prefix(i)
        # prefill rows: plain chunk bookkeeping + completion sampling from
        # the verify step's per-position logits (column lens-1)
        if prefill_rows or fbset:
            keys = self._sample_keys(pos, lens)
        if prefill_rows:
            for i in prefill_rows:
                if i in fbset:
                    continue      # emitted from the fallback logits below
                sl = self.slots[i]
                sl.pos += int(lens[i])
                sl.retries = 0
                if not sl.prefilling:
                    row_logits = logits_np[i, int(lens[i]) - 1]
                    if self.temperature <= 0.0:
                        t = int(np.argmax(row_logits))
                    else:
                        t = int(sample(keys[i], jnp.asarray(row_logits),
                                       self.temperature))
                    self._emit(i, t)
                    if len(sl.req.out) >= sl.req.max_new or sl.pos >= self.capacity - 1:
                        self._finish(i)
                        continue
                self._register_prefix(i)
        # quarantined rows: plain (γ=0) commit from the fallback step's
        # last-column logits — decode rows advance one token, prefill rows
        # advance their chunk
        for i in sorted(fbset):
            sl = self.slots[i]
            was_decoding = not sl.prefilling
            sl.pos += int(lens[i])
            sl.retries = 0
            if was_decoding or not sl.prefilling:
                if self.temperature <= 0.0:
                    t = int(np.argmax(fb_np[i]))
                else:
                    t = int(sample(keys[i], jnp.asarray(fb_np[i]),
                                   self.temperature))
                self._emit(i, t)
                if len(sl.req.out) >= sl.req.max_new or sl.pos >= self.capacity - 1:
                    self._finish(i)
                    continue
            self._register_prefix(i)
        self._rr = (self._rr + 1) % self.max_batch
        if tr.enabled:
            tr.complete("commit", PID_SCHED, TID_TICK, _ct, tr.ts() - _ct)
        return True

    def run(self, max_ticks: int = 100_000) -> list[Request]:
        """Drain the queue + all active slots; returns finished requests.

        Under :meth:`begin_drain` only active (and previously-admitted,
        preempted) work runs; everything still queued afterwards is rejected
        with SHUTTING_DOWN — no request ends without a terminal state."""
        ticks = 0
        while ticks < max_ticks:
            pending = self.admission.pending(admitted_only=self.draining)
            if not pending and not any(s is not None for s in self.slots):
                break
            if not self.tick() and not pending:
                break
            ticks += 1
        if self.draining:
            n = self.admission.flush_pending(RejectReason.SHUTTING_DOWN,
                                             self.clock)
            if n:
                log.info(kv("drain_flush", tick=self.clock, flushed=n))
        return self.finished

    # -------------------------------------------------------------- health
    def health(self) -> dict:
        """Robustness snapshot (DESIGN.md §10): ladder state + transitions,
        per-class queue depths, pool occupancy, and every shed / preempt /
        stall / fault counter. Pure host bookkeeping — cheap enough to call
        every tick.

        ``kernels`` surfaces the trace-time Pallas-vs-XLA path counters
        (kernels.ops): per-GEMM-name compiled paths and every explicit
        fallback with its reason, so a silent accelerator downgrade shows up
        in the health snapshot instead of only in wall-clock. The counters
        are process-global; this view diffs against the snapshot taken at
        THIS engine's construction, so co-hosted engines never see each
        other's trace events (§14 satellite fix).

        ``compiles`` counts the backend compiles (or persistent-cache loads)
        the process made since THIS engine's construction
        (obs/profile.watch_compiles); a served window that adds any stalled
        a tick on one.

        ``latency`` summarizes the wall-clock histograms (seconds): TTFT,
        inter-token, tick and queue-wait (submit to (re)admission)
        percentiles over every priority class."""
        mgr = self.mgr

        def _pct(h):
            return {"count": sum(c.count for c in h.children.values()),
                    **{f"p{p}": round(_family_percentile(h, p), 6)
                       for p in (50, 95, 99)}}

        return {
            "kernels": self._kops.kernel_counters_since(self._kernel_base),
            "compiles": compile_count() - self._compile_base,
            "latency": {"ttft_s": _pct(self._h_ttft),
                        "itl_s": _pct(self._h_itl),
                        "tick_s": _pct(self._h_tick),
                        "queue_wait_s": _pct(self._h_queue_wait)},
            "clock": self.clock,
            "ticks": self.ticks,
            "draining": self.draining,
            "ladder": self.ladder.snapshot(),
            "active_slots": sum(1 for s in self.slots if s is not None),
            "max_batch": self.max_batch,
            "queue_depths": self.admission.depths(),
            "queued": self.admission.pending(),
            "submitted": self.admission.submitted,
            "admitted": self.admission.admitted,
            "completed": len(self.finished),
            "rejections": self.admission.rejections_by_reason(),
            "sheds": self.admission.sheds,
            "preemptions": self.preemptions,
            "deadline_misses": self.deadline_misses,
            "pool": ({
                "pages": mgr.num_pages,
                "in_use": mgr.pages_in_use,
                "high_water": mgr.high_water,
                "live_pages": mgr.live_pages,
                "live_high_water": mgr.live_high_water,
                "occupancy": mgr.pages_in_use / max(mgr.num_pages, 1),
                "injected_alloc_failures": mgr.injected_failures,
            } if mgr is not None else {"layout": "dense"}),
            "prefix_cache": ({
                "enabled": True,
                "hits": self.prefix_hits,
                "tokens_reused": self.prefix_tokens_reused,
                "prefill_tokens_computed": self.prefill_tokens_computed,
                "cached_pages": mgr.cached_pages,
                "indexed_pages": len(mgr.prefix),
                "evictions": mgr.prefix.evictions,
                "cow_events": mgr.cow_events,
            } if (mgr is not None and mgr.prefix is not None)
                else {"enabled": False,
                      "prefill_tokens_computed": self.prefill_tokens_computed}),
            # sharding context accounting (satellite fixes): divisibility
            # replications are warned once + counted; rules whose mesh axes
            # were absent at use_mesh() time are reported, never vanished
            "sharding": ({
                "replicated_dims": self._shard_ctx.replicated_dims,
                "dropped_rules": dict(self._shard_ctx.dropped_rules),
            } if self._shard_ctx is not None else {"replicated_dims": 0,
                                                   "dropped_rules": {}}),
            "mesh": ({
                "dp": self.mesh.dp,
                "tp": self.mesh.tp,
                "devices": self.mesh.devices,
                "moe_dropped_tokens": self.moe_dropped_tokens,
                "comms": self.comms_summary(),
            } if self.mesh is not None else {"enabled": False}),
            "stalled_rows_total": self.stalled_rows_total,
            "stall_episodes": self.stall_episodes,
            "engine_stalls": self.engine_stalls,
            "idle_fault_ticks": self.idle_fault_ticks,
            "nan_events": self.nan_events,
            "fallback_retries": self.fallback_retries,
            "draft_stale_events": self.draft_stale_events,
            "draft_resyncs": self.draft_resyncs,
        }

    # ---------------------------------------------------------------- mesh
    def _accum_comms(self, snap: dict) -> None:
        """Fold one step's trace-time collective meter into running totals.

        The snapshot is static per compiled step width, so per-tick totals
        are exact — every tick at width W moved exactly the bytes the trace
        at width W recorded."""
        for key, r in snap.items():
            acc = self.comms.setdefault(key, {k: 0 for k in r})
            for k, v in r.items():
                acc[k] += v

    def _accum_device_load(self, dev: dict) -> None:
        for bits, m in dev.items():
            acc = self._device_weight.get(bits)
            self._device_weight[bits] = m if acc is None else acc + m

    def comms_summary(self) -> dict:
        """Interconnect rollup: {bits: {payload_bytes, bf16_bytes, elems,
        calls}} over all quantized-gather/amax-sync collectives so far, plus
        the grand totals core.report prices as interconnect energy."""
        by_bits: dict = {}
        for (_, bits), r in self.comms.items():
            acc = by_bits.setdefault(
                int(bits),
                {"calls": 0, "elems": 0, "payload_bytes": 0,
                 "scale_bytes": 0, "bf16_bytes": 0},
            )
            for k, v in r.items():
                acc[k] += v
        total = sum(r["payload_bytes"] + r["scale_bytes"] for r in by_bits.values())
        bf16 = sum(r["bf16_bytes"] for r in by_bits.values())
        return {"by_bits": by_bits, "bytes_moved": total, "bf16_bytes": bf16}

    def device_attribution(self) -> dict:
        """Per-device share of the engine's cycle totals: {bits: (dp, tp)
        int64}, split proportionally to each device's own executed serial
        cycles and summing *exactly* to ``cycles_by_bits`` (the same totals
        a single-device run books into its SlotMeters — the PR's
        attribution gate). Requires mesh + track_energy."""
        if self.mesh is None:
            raise ValueError("device_attribution() needs a mesh scheduler")
        from ..parallel.serve_mesh import ShardedStep

        out = {}
        for bits, acc in self.cycles_by_bits.items():
            w = self._device_weight.get(bits)
            if w is None:
                w = np.ones((self.mesh.dp, self.mesh.tp), np.int64)
            shares = ShardedStep.split_exact(acc["serial_cycles"], w.reshape(-1))
            out[bits] = shares.reshape(self.mesh.dp, self.mesh.tp)
        return out

    # -------------------------------------------------------------- energy
    def energy_summary(self, variant: str = "serial") -> list[dict]:
        """Per-request {rid, tokens, cycles, cycles_by_bits, latency_s,
        energy_j} — finished requests first, then in-flight slots.
        Requires ``track_energy=True``."""
        active = [s.meter for s in self.slots if s is not None and s.meter is not None]
        return [m.energy(variant) for m in self.finished_meters + active]

    def spec_summary(self, variant: str = "serial") -> dict:
        """Speculative-decoding rollup: acceptance rate + the draft-vs-verify
        energy split and energy-per-accepted-token (core.report). Requires
        ``track_energy=True`` for the energy fields; the token counters are
        always live."""
        from ..core.report import spec_energy_summary

        out = spec_energy_summary(self.energy_summary(variant))
        out.update(
            spec_gamma=self.spec.gamma if self.spec is not None else 0,
            draft_policy=self.spec.describe_draft() if self.spec is not None else None,
            ticks=self.ticks,
            drafted_tokens=self.drafted_tokens,
            accepted_draft_tokens=self.accepted_draft_tokens,
            acceptance_rate=(self.accepted_draft_tokens / self.drafted_tokens
                             if self.drafted_tokens else 0.0),
        )
        return out

    # --------------------------------------------------------------- stats
    def cache_stats(self) -> dict:
        """Live-vs-reserved cache accounting for benchmarks."""
        from .cache import cache_bytes, dense_cache_tokens

        total = cache_bytes(self.caches)
        if self.spec is not None:
            # the draft pool is real memory: report it alongside (same page
            # high-water — one BlockManager backs both pools)
            total += cache_bytes(self.spec.caches)
        if self.mgr is not None:
            frac = self.mgr.high_water / max(self.mgr.num_pages, 1)
            out = {
                "layout": "paged",
                "pool_pages": self.mgr.num_pages,
                "high_water_pages": self.mgr.high_water,
                "live_high_water_pages": self.mgr.live_high_water,
                "cache_bytes_reserved": total,
                "cache_bytes_high_water": int(total * frac),
            }
            if self.mgr.prefix is not None:
                out.update(
                    prefix_hits=self.prefix_hits,
                    prefix_tokens_reused=self.prefix_tokens_reused,
                    prefill_tokens_computed=self.prefill_tokens_computed,
                    prefix_cached_pages=self.mgr.cached_pages,
                    prefix_evictions=self.mgr.prefix.evictions,
                    cow_events=self.mgr.cow_events,
                )
            return out
        return {
            "layout": "dense",
            "reserved_tokens": dense_cache_tokens(self.max_batch, self.capacity),
            "cache_bytes_reserved": total,
            "cache_bytes_high_water": total,
        }


# Registry-backed views over the legacy counter attributes (see
# _SCHED_COUNTERS). Installed on the class so instance assignment
# (``self.ticks = 0`` / ``+= 1``) routes through the property setter.
for _a in _SCHED_COUNTERS:
    setattr(Scheduler, _a, _counter_property(_a))
del _a


def install_sigint_drain(sched: Scheduler):
    """Graceful shutdown (satellite b): the first SIGINT begins a drain —
    active slots finish, queued work is rejected with structured
    SHUTTING_DOWN, SlotMeter energy summaries survive for the final flush;
    a second SIGINT restores the previous handler and raises
    KeyboardInterrupt (hard abort). Returns a zero-arg callable that
    restores the previous handler."""
    import signal

    prev = signal.getsignal(signal.SIGINT)

    def _handler(signum, frame):
        if sched.draining:
            signal.signal(signal.SIGINT, prev)
            raise KeyboardInterrupt
        log.warning(kv(
            "sigint_drain", tick=sched.clock,
            active=sum(1 for s in sched.slots if s is not None),
            queued=sched.admission.pending(),
            hint="^C again to abort",
        ))
        sched.begin_drain()

    signal.signal(signal.SIGINT, _handler)

    def restore():
        signal.signal(signal.SIGINT, prev)

    return restore
