"""Sharded multi-device serving: the scheduler's ONE mixed step on a mesh.

``build_sharded_step`` wraps the same ``(max_batch, prefill_chunk)`` mixed
prefill+decode step the single-device Scheduler jits — but ``shard_map``-ped
over a ``(dp, tp)`` mesh (``--xla_force_host_platform_device_count=8`` makes
an 8-device CPU mesh CI-testable). Layout:

- **dp** shards the batch: each dp group owns a contiguous row range; the
  host-side planner (and BlockManager) stay device-agnostic — inputs arrive
  replicated and the body slices its own rows.
- **tp** shards attention by head group (GQA: Q and KV heads together, so
  the per-head Q→KV group mapping is device-local; MLA: absorbed-Q heads,
  the latent KV has no head axis and replicates), dense-FFN columns, and
  MoE experts (expert parallelism). The paged KV pool and block tables are
  head-group sharded over tp and replicated over dp (pages are shared by
  rows, so every device writes every row's tokens — the dp row gather ships
  *already-quantized* int8 planes).
- Weights of the **gathered** GEMMs (o-proj, down-proj) stay replicated:
  their inputs are tp-sharded features, re-assembled by the
  quantize-before-all-gather collectives in ``parallel.collectives`` — the
  wire carries the layer's policy bits, not bf16.

Bit-exactness contract (the PR gate): every quantization scale is the
mesh-global amax (``lax.pmax`` of local amaxes — max-merge is exact),
gathered integer planes equal the single-device quantization of the full
row, expert combine gathers at full precision, and the tuGEMM statistics
merge across devices by max (non-expert; separability of
``max_a·max(max_b,1)``) or dp-max + tp-concat (expert-parallel GEMMs) with
serial/parallel recomputed from the merged step cycles — so greedy tokens
AND cycle totals are bit-identical to the single-device run.

Allocator state (BlockManager) stays host-global: page allocation is
sequential, content-addressed (prefix cache) and fault-injected — one
authoritative host copy forked per-device would either diverge or need a
consensus protocol; a single host table uploaded once per version is
correct by construction and costs one small int32 transfer per mutation.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental.shard_map import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ModelConfig, RunConfig
from ..core.tugemm import TuGemmStats
from ..models.attention import KVView
from ..models.transformer import forward, lm_logits
from ..obs.profile import named_scope
from ..quant import capture as stats_capture
from . import collectives as dist
from .sharding import suspend_mesh

__all__ = [
    "MeshSpec",
    "as_spec",
    "mesh_for",
    "validate",
    "local_config",
    "param_pspecs",
    "cache_pspecs",
    "shard_params",
    "shard_caches",
    "build_sharded_step",
    "ShardedStep",
    "GATHER_GEMMS",
    "EXPERT_GEMMS",
    "COL_OUT_GEMMS",
]


# GEMMs whose input features are tp-sharded (the upstream GEMM was
# column-parallel) — these run quantize-before-all-gather:
GATHER_GEMMS = frozenset({"attn.o", "mla.o", "mlp.down"})
# expert-parallel GEMMs: stats merge by dp-max + tp-concat over experts
EXPERT_GEMMS = frozenset({"moe.gate", "moe.up", "moe.down"})
# column-parallel GEMMs: their N in the merged metadata is N_local * tp
COL_OUT_GEMMS = frozenset(
    {"attn.q", "attn.k", "attn.v", "mla.q", "mlp.gate", "mlp.up"}
)


@dataclass(frozen=True)
class MeshSpec:
    """A (dp, tp) serving mesh request."""

    dp: int = 1
    tp: int = 1
    dp_axis: str = "data"
    tp_axis: str = "model"

    @property
    def devices(self) -> int:
        return self.dp * self.tp


def as_spec(mesh) -> MeshSpec:
    """Coerce a MeshSpec | (dp, tp) | "dp,tp" into a MeshSpec."""
    if isinstance(mesh, MeshSpec):
        return mesh
    if isinstance(mesh, str):
        parts = [int(v) for v in mesh.split(",")]
        if len(parts) != 2:
            raise ValueError(f"--mesh wants 'dp,tp', got {mesh!r}")
        return MeshSpec(parts[0], parts[1])
    if isinstance(mesh, (tuple, list)) and len(mesh) == 2:
        return MeshSpec(int(mesh[0]), int(mesh[1]))
    raise TypeError(f"cannot interpret mesh spec {mesh!r}")


_MESH_CACHE: dict[MeshSpec, Mesh] = {}


def mesh_for(spec: MeshSpec) -> Mesh:
    if spec not in _MESH_CACHE:
        _MESH_CACHE[spec] = jax.make_mesh(
            (spec.dp, spec.tp), (spec.dp_axis, spec.tp_axis)
        )
    return _MESH_CACHE[spec]


def validate(cfg: ModelConfig, rc: RunConfig, spec: MeshSpec, max_batch: int) -> None:
    """Fail loudly on any divisibility the sharded layout relies on.

    (Silent replicate-on-non-dividing is fine for training layouts —
    parallel.sharding warns and counts — but here the collective program is
    static: a gather over features that were never sharded would be wrong,
    not slow, so the mesh step refuses to build.)"""
    n = jax.device_count()
    if spec.devices > n:
        raise ValueError(f"mesh {spec.dp}x{spec.tp} wants {spec.devices} devices, "
                         f"only {n} available (set XLA_FLAGS="
                         f"--xla_force_host_platform_device_count=N on CPU)")
    if max_batch % spec.dp != 0:
        raise ValueError(f"max_batch {max_batch} not divisible by dp={spec.dp}")
    if spec.tp > 1:
        if cfg.attn_type == "gqa":
            if cfg.num_heads % spec.tp or cfg.num_kv_heads % spec.tp:
                raise ValueError(
                    f"tp={spec.tp} must divide num_heads={cfg.num_heads} and "
                    f"num_kv_heads={cfg.num_kv_heads} (head-group KV sharding)")
        elif cfg.attn_type == "mla":
            if cfg.num_heads % spec.tp:
                raise ValueError(
                    f"tp={spec.tp} must divide num_heads={cfg.num_heads}")
        has_dense_ffn = any(
            not cfg.is_moe_layer(i) for i in range(cfg.num_layers))
        if has_dense_ffn and cfg.d_ff % spec.tp:
            raise ValueError(f"tp={spec.tp} must divide d_ff={cfg.d_ff}")
        if cfg.num_experts and cfg.num_experts % spec.tp:
            raise ValueError(
                f"tp={spec.tp} must divide num_experts={cfg.num_experts}")


def local_config(cfg: ModelConfig, spec: MeshSpec) -> ModelConfig:
    """The per-device model view: head counts divided by tp (the reshape
    constants inside the attention layers must match the column-sharded
    projections). ``head_dim`` is pinned to the *global* resolved value —
    otherwise ``d_model // num_heads_local`` would silently change it.
    Expert count stays global: the router and dispatch see every expert;
    only the expert GEMM slabs are sharded (sliced by shape in moe_ffn)."""
    if spec.tp == 1:
        return cfg
    if cfg.attn_type == "gqa":
        return cfg.replace(
            num_heads=cfg.num_heads // spec.tp,
            num_kv_heads=cfg.num_kv_heads // spec.tp,
            head_dim=cfg.resolved_head_dim,
        )
    if cfg.attn_type == "mla":
        return cfg.replace(num_heads=cfg.num_heads // spec.tp)
    return cfg


# ------------------------------------------------------------ partition specs
def _axis_spec(rank: int, assign: dict) -> P:
    return P(*(assign.get(i) for i in range(rank)))


def _path_keys(path) -> list[str]:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(str(p.idx))
        elif hasattr(p, "name"):
            out.append(str(p.name))
        else:
            out.append(str(p))
    return out


def _param_pspec(spec: MeshSpec, keys: list[str], leaf) -> P:
    """Partition rule for one param leaf, by its path in the model tree.

    - column-parallel first GEMMs (wq/wk/wv, mla wq, mlp gate/up): output
      (last) axis over tp — kernel, qkernel, qscale and bias alike;
    - MLA absorbed projections w_uk/w_uv (L, lora, heads, hd'): heads axis;
    - MoE expert slabs (L, E, ...): experts axis (expert parallelism);
    - everything else (norms, embeddings, router, shared experts, the
      gathered GEMMs' weights, lm head) replicates.
    """
    tp = spec.tp_axis
    shape = getattr(leaf, "shape", ())
    if spec.tp == 1 or not shape:
        return P()
    name = keys[-1]
    parent = keys[-2] if len(keys) >= 2 else ""
    if "experts" in keys and "shared" not in keys:
        if len(shape) >= 2 and shape[1] % spec.tp == 0:
            return _axis_spec(len(shape), {1: tp})
        return P()
    col_parents = {"wq", "wk", "wv", "w_gate", "w_up"}
    if "shared" not in keys and parent in col_parents and name in (
        "kernel", "qkernel", "qscale", "bias"
    ):
        ax = len(shape) - 1
        if shape[ax] % spec.tp == 0:
            return _axis_spec(len(shape), {ax: tp})
        return P()
    if parent in ("w_uk", "w_uv") and name == "kernel":
        if len(shape) >= 3 and shape[2] % spec.tp == 0:
            return _axis_spec(len(shape), {2: tp})
    return P()


def param_pspecs(spec: MeshSpec, params):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _param_pspec(spec, _path_keys(path), leaf), params
    )


def _cache_pspec(spec: MeshSpec, rc: RunConfig, name: str, leaf) -> P:
    """KV cache partition: paged pools replicate over dp (pages are shared
    by all rows); dense layouts shard batch over dp (axis 1). GQA ``k``/``v``
    leaves shard their heads over tp on axis 3: the head-major kv*hd feature
    axis of a paged pool (L, P+1, bs, kv*hd), whose tp blocks are whole
    heads since tp divides kv (``validate``), or the kv axis of a dense
    (L, B, cap, kv, hd) buffer. MLA latents (``ckv``, ``kr``), SSM state and
    the per-token scale planes have no head axis and replicate over tp."""
    shape = getattr(leaf, "shape", ())
    assign: dict = {}
    if rc.kv_layout != "paged" and len(shape) >= 2 and spec.dp > 1 \
            and shape[1] % spec.dp == 0:
        assign[1] = spec.dp_axis
    if name in ("k", "v") and spec.tp > 1 and shape[3] % spec.tp == 0:
        assign[3] = spec.tp_axis
    return _axis_spec(len(shape), assign) if assign else P()


def cache_pspecs(spec: MeshSpec, rc: RunConfig, caches):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _cache_pspec(spec, rc, _path_keys(path)[-1], leaf), caches
    )


def _place(mesh: Mesh, tree, pspecs):
    return jax.device_put(
        tree, jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                           is_leaf=lambda x: isinstance(x, P))
    )


def shard_params(spec: MeshSpec, params):
    return _place(mesh_for(spec), params, param_pspecs(spec, params))


def shard_caches(spec: MeshSpec, rc: RunConfig, caches):
    return _place(mesh_for(spec), caches, cache_pspecs(spec, rc, caches))


# ------------------------------------------------------------- sharded step
class ShardedStep:
    """Callable handle around the jitted shard_map step + its host-side
    merge/attribution helpers. Calling it returns ``(caches, logits,
    raw_tree)`` where raw_tree carries per-device stats with leading
    (dp, tp) axes — feed it to :meth:`merge_stats` /
    :meth:`device_serial_by_bits` / :meth:`moe_drops`."""

    def __init__(self, cfg: ModelConfig, rc: RunConfig, spec: MeshSpec):
        self.cfg, self.rc, self.spec = cfg, rc, spec
        self.mesh = mesh_for(spec)
        self.ep = spec.tp > 1 and cfg.num_experts > 0
        self.fn = None            # set by build_sharded_step
        self._meters: dict[int, dict] = {}  # step width -> meter snapshot

    def __call__(self, params, caches, tokens, pos, lens, tables):
        return self.fn(params, caches, tokens, pos, lens, tables)

    # ----------------------------------------------------------- comms meter
    def comms_for(self, width: int) -> dict:
        """Trace-time comms snapshot for a step of this token width:
        {(label, bits): {calls, elems, payload_bytes, scale_bytes,
        bf16_bytes}} — static per compiled width, recorded at trace time."""
        return self._meters.get(width, {})

    # ----------------------------------------------------------- stats merge
    def _merge_gemm(self, e: stats_capture.CapturedGemm) -> stats_capture.CapturedGemm:
        st = e.stats
        step = np.asarray(st.step_cycles)          # (dp, tp, *lead, K)
        ma = np.asarray(st.max_abs)
        am = None if st.act_max is None else np.asarray(st.act_max)
        base = e.name.split("#")[0]
        if base in EXPERT_GEMMS and self.ep:
            # expert-parallel: device t holds experts [t·E_l, (t+1)·E_l) on
            # the dp-local rows — max over dp, concatenate over tp along the
            # experts axis (step: axis -2; scalar stats: axis -1)
            step = step.max(axis=0)
            step = np.concatenate(list(step), axis=-2)
            ma = ma.max(axis=0)
            ma = np.concatenate(list(ma), axis=-1)
            if am is not None:
                am = am.max(axis=0)
                am = np.concatenate(list(am), axis=-1)
            M, N = e.M * self.spec.dp, e.N
        else:
            # row/column partition of one GEMM: step_cycles[k] =
            # max_a[k]·max(max_b[k],1) with max_a over dp-local rows and
            # max_b over tp-local columns — both factors nonnegative, so the
            # max over the device grid factorizes to the global product
            step = step.max(axis=(0, 1))
            ma = ma.max(axis=(0, 1))
            if am is not None:
                am = am.max(axis=(0, 1))
            M = e.M * self.spec.dp
            N = e.N * self.spec.tp if base in COL_OUT_GEMMS else e.N
        stats = TuGemmStats(
            step_cycles=step,
            serial_cycles=step.sum(axis=-1),
            parallel_cycles=step.max(axis=-1),
            max_abs=ma,
            act_max=am,
        )
        return stats_capture.CapturedGemm(e.name, int(M), e.K, int(N), stats, e.bits)

    def merge_stats(self, raw):
        """Per-device raw stats tree -> the tree the single-device step would
        have produced (bit-identical cycle totals — the attribution gate)."""

        def walk(node):
            if isinstance(node, stats_capture.CapturedGemm):
                return self._merge_gemm(node)
            if isinstance(node, stats_capture.CapturedScalar):
                v = np.asarray(node.value)     # (dp, tp, ...)
                return stats_capture.CapturedScalar(node.name, v[:, 0].sum(axis=0))
            if isinstance(node, dict):
                return {k: walk(v) for k, v in node.items()}
            if isinstance(node, (list, tuple)):
                return type(node)(walk(v) for v in node)
            return node

        return walk(raw)

    def device_serial_by_bits(self, raw) -> dict[int, np.ndarray]:
        """Per-device serial-cycle load from the raw tree:
        {bits: (dp, tp) int64} — each device's own executed cycles (its row
        and column shards), the balance signal for the bench report."""
        out: dict[int, np.ndarray] = {}
        for _, e in stats_capture.tree_entries(raw):
            s = np.asarray(e.stats.serial_cycles, dtype=np.int64)
            s = s.reshape(s.shape[0], s.shape[1], -1).sum(axis=-1)
            acc = out.setdefault(
                int(e.bits), np.zeros((self.spec.dp, self.spec.tp), np.int64))
            acc += s
        return out

    def moe_drops(self, raw) -> int:
        """Total router capacity drops this step (counted once per dp group:
        tp replicas compute identical dispatches)."""
        total = 0
        for name, s in stats_capture.tree_scalars(raw):
            if name.endswith("moe.dropped_tokens"):
                v = np.asarray(s.value)
                total += int(v[:, 0].sum())
        return total

    @staticmethod
    def split_exact(total: int, weights) -> np.ndarray:
        """Split integer ``total`` proportionally to ``weights`` such that
        the shares are integers and sum to exactly ``total`` (cumulative
        floor differences — no rounding drift)."""
        w = np.asarray(weights, np.float64).reshape(-1)
        if w.sum() <= 0:
            w = np.ones_like(w)
        cum = np.floor(int(total) * np.cumsum(w) / w.sum()).astype(np.int64)
        cum[-1] = int(total)
        return np.diff(np.concatenate([np.zeros(1, np.int64), cum]))


def build_sharded_step(
    cfg: ModelConfig,
    rc: RunConfig,
    spec: MeshSpec,
    params,
    caches,
    *,
    with_stats: bool = False,
    donate: bool = True,
) -> ShardedStep:
    """Build the shard_map-ped mixed step. ``params``/``caches`` are only
    read for tree structure + shapes (partition specs); pass the real
    (already placed) trees. Returns a :class:`ShardedStep`; calling it is
    drop-in for the single-device step except the output is always the
    3-tuple ``(caches, logits, raw_stats_tree)`` (a scalars-only capture
    keeps the MoE drop counter flowing even when energy tracking is off)."""
    mesh = mesh_for(spec)
    cfg_local = local_config(cfg, spec)
    p_specs = param_pspecs(spec, params)
    c_specs = cache_pspecs(spec, rc, caches)
    paged = rc.kv_layout == "paged"
    kv_sync = frozenset({"k", "v"}) if cfg.attn_type == "gqa" and spec.tp > 1 else frozenset()
    handle = ShardedStep(cfg, rc, spec)

    def body(params, caches, tokens, pos, lens, tables):
        B, W = tokens.shape
        b_local = B // spec.dp
        d = lax.axis_index(spec.dp_axis)

        def rows(a):
            return lax.dynamic_slice_in_dim(a, d * b_local, b_local, axis=0)

        tok_l, pos_l, lens_l = rows(tokens), rows(pos), rows(lens)
        tab_l = rows(tables) if tables is not None else None
        view = KVView(pos_l, lens_l, tab_l, rc.block_size, rc.kv_layout)
        write_view = None
        if paged and tables is not None:
            # full-batch addressing for the dp-replicated page pool: every
            # device writes every row's pages (values gathered over dp)
            write_view = KVView(pos, lens, tables, rc.block_size, rc.kv_layout)
        prog = dist.MeshProgram(
            dp_axis=spec.dp_axis, tp_axis=spec.tp_axis, dp=spec.dp, tp=spec.tp,
            gather_gemms=GATHER_GEMMS, expert_gemms=EXPERT_GEMMS,
            kv_sync_names=kv_sync, write_view=write_view,
        )
        batch = {"tokens": tok_l}
        if cfg.mrope_sections is not None:
            pp = pos_l[:, None] + jnp.broadcast_to(
                jnp.arange(W, dtype=jnp.int32), (b_local, W))
            batch["positions"] = jnp.stack([pp, pp, pp])
        with suspend_mesh(), dist.activate(prog):
            with stats_capture.capture_stats(scalars_only=not with_stats) as cap:
                # serve/* named scopes: the device profile (obs/profile.py
                # device_trace) lines sharded kernels up against the host
                # tick timeline by name, same taxonomy as the 1-device step
                with named_scope("serve/step"):
                    h, caches, _ = forward(
                        cfg_local, rc, params, batch,
                        caches=caches, cache_pos=pos_l, kv_view=view,
                    )
                    with named_scope("serve/logits"):
                        idx = jnp.clip(lens_l - 1, 0, W - 1)
                        h_last = jnp.take_along_axis(
                            h, idx[:, None, None], axis=1)
                        logits = lm_logits(cfg_local, rc, params, h_last)[:, 0, :]
        # every stats leaf gains leading (dp, tp) device axes so one
        # P(dp, tp) prefix out_spec covers the whole (trace-dependent) tree
        tree = jax.tree.map(lambda a: a[None, None], cap.tree)
        handle._meters[W] = prog.meter_snapshot()   # static; trace-time only
        return caches, logits, tree

    mapped = shard_map(
        body,
        mesh=mesh,
        in_specs=(p_specs, c_specs, P(), P(), P(), P()),
        out_specs=(c_specs, P(spec.dp_axis), P(spec.dp_axis, spec.tp_axis)),
        check_rep=False,
    )
    handle.fn = jax.jit(mapped, donate_argnums=(1,)) if donate else jax.jit(mapped)
    return handle
