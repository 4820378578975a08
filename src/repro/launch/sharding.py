"""Sharding rules: param/activation PartitionSpecs for the production mesh.

Strategy (see DESIGN.md §4): Megatron-style tensor parallelism on the
"model" axis + ZeRO/FSDP sharding of the complementary weight dim on the
"data" axis + pure data parallelism on the "pod" axis, with sequence
parallelism (residual activations sharded on seq over "model") bounding
activation memory for the 4k/32k shapes.

Head counts that don't divide the TP degree are padded (llava 56->64,
granite-3b 24->32; zero-initialized wo rows keep the function exact); KV
projections replicate on the model axis when kv_heads doesn't divide it.
Every rule degrades to replication when a dim isn't divisible, so the same
rules serve reduced smoke configs and small test meshes.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.tracing import count


def pad_heads(cfg: ModelConfig, tp: int) -> ModelConfig:
    """Pad num_heads up to a multiple of tp (keeping GQA grouping legal)."""
    h = cfg.num_heads
    if h % tp == 0 or cfg.family == "ssm":
        return cfg
    hp = ((h + tp - 1) // tp) * tp
    # keep grouping divisible: hp must be a multiple of kv heads
    while hp % cfg.num_kv_heads:
        hp += tp
    return dataclasses.replace(cfg, num_heads=hp)


@dataclass(eq=False)  # identity hash: used as a custom_vjp nondiff arg
class ShardingPolicy:
    mesh: Mesh
    cfg: ModelConfig

    def __post_init__(self):
        names = self.mesh.axis_names
        sizes = dict(zip(names, self.mesh.devices.shape))
        self.tp = "model" if "model" in names else None
        self.tp_size = sizes.get("model", 1)
        dp = tuple(a for a in ("pod", "data") if a in names)
        self.dp = dp if len(dp) > 1 else (dp[0] if dp else None)
        self.dp_size = int(np.prod([sizes[a] for a in ("pod", "data")
                                    if a in names]))
        self.fsdp = "data" if "data" in names else None
        self.fsdp_size = sizes.get("data", 1)
        self.all_axes = tuple(names)
        self.total = int(np.prod(self.mesh.devices.shape))

    # -- helpers -----------------------------------------------------------
    def _div(self, dim: int, axis, size: int):
        """axis if dim divides evenly, else None (replicate)."""
        return axis if axis is not None and dim % size == 0 and size > 1 else None

    def named(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def constrain(self, x, spec: P):
        return lax.with_sharding_constraint(x, self.named(spec))

    # -- parameter specs ----------------------------------------------------
    def param_spec(self, path: tuple, leaf) -> P:
        cfg = self.cfg
        names = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
        last = names[-1]
        shape = leaf.shape
        stacked = ("layers" in names or "enc_layers" in names
                   or "tail_layers" in names)
        pre = (None,) if stacked else ()
        tp, fsdp = self.tp, self.fsdp

        def spec(*dims):
            return P(*pre, *dims)

        if last == "table":  # embedding [V, d]
            # vocab on TP when divisible (best measured temp), else FSDP on d;
            # the gather is done in bf16 (see layers.embed) so the reshard of
            # its output never spills f32 copies.
            v_ax = self._div(shape[0], tp, self.tp_size)
            if v_ax:
                return P(v_ax, self._div(shape[1], fsdp, self.fsdp_size))
            return P(None, self._div(shape[1], fsdp, self.fsdp_size))
        if names[-2] == "unembed":  # [d, V]
            return P(self._div(shape[0], fsdp, self.fsdp_size),
                     self._div(shape[1], tp, self.tp_size))
        if last in ("wq",):
            return spec(self._div(shape[-2], fsdp, self.fsdp_size),
                        self._div(shape[-1], tp, self.tp_size))
        if last in ("wk", "wv"):
            kv_ok = cfg.num_kv_heads % self.tp_size == 0
            return spec(self._div(shape[-2], fsdp, self.fsdp_size),
                        tp if kv_ok and self.tp_size > 1 else None)
        if last == "wo":
            return spec(self._div(shape[-2], tp, self.tp_size),
                        self._div(shape[-1], fsdp, self.fsdp_size))
        if last in ("gate", "up"):
            if len(shape) == len(pre) + 3:  # MoE experts [*, E, d, ffe]
                return spec(self._div(shape[-3], tp, self.tp_size),
                            self._div(shape[-2], fsdp, self.fsdp_size), None)
            return spec(self._div(shape[-2], fsdp, self.fsdp_size),
                        self._div(shape[-1], tp, self.tp_size))
        if last == "down":
            if len(shape) == len(pre) + 3:  # MoE [*, E, ffe, d]
                return spec(self._div(shape[-3], tp, self.tp_size), None,
                            self._div(shape[-1], fsdp, self.fsdp_size))
            return spec(self._div(shape[-2], tp, self.tp_size),
                        self._div(shape[-1], fsdp, self.fsdp_size))
        if last == "router":
            return spec(self._div(shape[-2], fsdp, self.fsdp_size), None)
        if last in ("w_z", "w_x"):  # [*, d, d_inner] head-parallel
            return spec(self._div(shape[-2], fsdp, self.fsdp_size),
                        self._div(shape[-1], tp, self.tp_size))
        if last in ("w_B", "w_C"):  # group-shared: replicate state dim
            return spec(self._div(shape[-2], fsdp, self.fsdp_size), None)
        if last == "w_dt":
            return spec(self._div(shape[-2], fsdp, self.fsdp_size),
                        self._div(shape[-1], tp, self.tp_size))
        if last == "conv_x":
            return spec(None, self._div(shape[-1], tp, self.tp_size))
        if last in ("conv_B", "conv_C"):
            return spec(None, None)
        if last in ("dt_bias", "A_log", "D"):
            return spec(self._div(shape[-1], tp, self.tp_size))
        if last == "out_proj":  # [*, d_inner, d]
            return spec(self._div(shape[-2], tp, self.tp_size),
                        self._div(shape[-1], fsdp, self.fsdp_size))
        if last == "norm_scale":
            return spec(self._div(shape[-1], tp, self.tp_size))
        if last == "scale":  # RMSNorm
            return spec(None)
        # default: replicate
        return P(*((None,) * len(shape)))

    def param_shardings(self, params):
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: self.named(self.param_spec(path, leaf)), params
        )

    def param_specs(self, params):
        return jax.tree_util.tree_map_with_path(self.param_spec, params)

    # -- activation specs ---------------------------------------------------
    @property
    def seq_spec(self) -> P:
        """Residual stream [B, S, d]: batch on DP, seq on TP (Megatron SP)."""
        return P(self.dp, self.tp, None)

    def batch_spec(self, batch_size: int, seq_len: int) -> P:
        """Token batches [B, S]."""
        dp = self.dp if batch_size % self.dp_size == 0 else None
        s = self.tp if seq_len % max(self.tp_size, 1) == 0 else None
        return P(dp, s)

    def token_spec(self, batch_size: int) -> P:
        return P(self.dp if batch_size % self.dp_size == 0 else None)

    def kv_cache_spec(self, batch_size: int, seq_len: int) -> P:
        """[L, B, S, KV, hd]: batch on DP, seq on TP; batch-1 long-context
        shards seq over every axis (256/512-way context parallelism)."""
        if batch_size == 1:
            all_sz = self.total
            s = self.all_axes if seq_len % all_sz == 0 else (
                self.tp if seq_len % self.tp_size == 0 else None)
            return P(None, None, s, None, None)
        dp = self.dp if batch_size % self.dp_size == 0 else None
        s = self.tp if seq_len % max(self.tp_size, 1) == 0 else None
        return P(None, dp, s, None, None)

    def ssm_cache_spec(self, field: str, batch_size: int, leaf) -> P:
        dp = self.dp if batch_size % self.dp_size == 0 else None
        if field == "state":  # [L, B, H, P, N]
            h = self.tp if leaf.shape[2] % max(self.tp_size, 1) == 0 else None
            return P(None, dp, h, None, None)
        if field == "conv_x":  # [L, B, K-1, d_inner]
            c = self.tp if leaf.shape[3] % max(self.tp_size, 1) == 0 else None
            return P(None, dp, None, c)
        return P(None, dp, None, None)  # conv_B / conv_C

    def cache_shardings(self, cache, batch_size: int):
        """Map a decode cache pytree to NamedShardings (shape-aware)."""

        def spec_for(path, leaf):
            names = [getattr(k, "key", str(k)) for k in path]
            if "kv" in names or "cross" in names:
                return self.named(
                    self.kv_cache_spec(batch_size, leaf.shape[2]))
            return self.named(self.ssm_cache_spec(names[-1], batch_size, leaf))

        return jax.tree_util.tree_map_with_path(spec_for, cache)

    def logits_spec(self, batch_size: int) -> P:
        dp = self.dp if batch_size % self.dp_size == 0 else None
        v = self.tp if self.cfg.vocab_size % max(self.tp_size, 1) == 0 else None
        return P(dp, v)

    def collective_planner(self, topo, registry=None) -> "MeshCollectivePlanner":
        """A planner for this policy's mesh over the physical fabric."""
        return MeshCollectivePlanner(
            topo,
            dict(zip(self.mesh.axis_names, self.mesh.devices.shape)),
            registry=registry,
        )


# ---------------------------------------------------------------------------
# Mesh-axis collectives through the algorithm registry
# ---------------------------------------------------------------------------

# An all-reduce asked for by name splits each member's shard in two
# (``CollectiveRequest.chunks=2``: halves around opposite orientations of
# the fabric) when its chunk is larger than this, in MiB. On the v5e 2x2 a
# split turns 4 waves of one S/4 chunk into 6 waves of S/8, so the busiest
# link carries 0.75 S instead of S: it saves one chunk's transfer at one
# link's ~48 GB/s (137 us a 6.25 MiB chunk). It costs 4 more rounds of
# copies at ~0.82 us and 2 more waves at ~0.3 us of fixed permute cost,
# ~3.9 us (v5e 2x2 readings: a 96 KiB round's copies, and a permute's
# time above its bytes). Break-even: 3.9 us * 48 GB/s = 0.18 MiB. A
# gradient bucket's chunk (MiBs) splits; a decode activation's (0.09 MiB)
# does not.
SPLIT_ALL_REDUCE_MIB = 0.18


class MeshCollectivePlanner:
    """Routes per-mesh-axis process-group collectives through the shared
    :class:`repro.core.registry.AlgorithmRegistry`.

    A (data, model) mesh laid row-major on the physical torus induces one
    process group per row of every axis: ``model``-axis groups vary the last
    axis, ``data``-axis groups the first, etc. All groups of one axis are
    isomorphic under the torus translations, so the registry synthesizes each
    (axis, collective, bytes) combination exactly once and serves every other
    row by relabeling, instead of synthesizing every row anew.

    ``axis_sizes`` is an ordered {axis name: size} whose product must equal
    the NPU count; device index = row-major rank, assumed to coincide with
    the topology's NPU ids (true for ``tpu_v5e_pod``/``torus2d`` meshes, and
    for ``multi_pod`` meshes whose leading axis is the pod axis).

    On partitioned fabrics (``multi_pod`` et al), groups that span pods —
    e.g. the data-parallel axis of a ("pod", "data", "model") mesh — are
    routed through the hierarchical synthesis pipeline automatically (the
    engine's ``hierarchy="auto"``): per-pod phases are synthesized once per
    canonical pod and stitched with an inter-pod phase, instead of paying a
    flat whole-fabric TEN search per group. This covers the reduction
    collectives too — a pod-spanning ``reduce_scatter`` synthesizes as the
    time-reversal of a hierarchical All-Gather on the reversed fabric, and
    ``all_reduce`` composes that with the forward hierarchical All-Gather —
    so the data-parallel gradient path, the dominant collective of
    multi-pod training, takes the scalable route by default. A request with
    ``hierarchy="never"`` forces flat synthesis.

    Fabrics carrying a nested partition tree (``three_level`` et al —
    rack -> pod -> plane) recurse: a plane-spanning group decomposes into a
    plane phase over pod gateways, per-pod phases that themselves decompose
    into rack phases, and canonical per-rack plans registry-shared across
    every isomorphic rack of every pod. ``hierarchy_levels()`` reports how
    deep the routing goes.
    """

    def __init__(self, topo, axis_sizes: dict[str, int], *, registry=None,
                 gateway_strategy: str = "auto", sketch=None):
        from repro.core.engine import SynthesisEngine
        from repro.core.registry import default_registry

        self.topo = topo
        self.axis_sizes = dict(axis_sizes)
        shape = tuple(self.axis_sizes.values())
        if int(np.prod(shape)) != len(topo.npus):
            raise ValueError(
                f"mesh {self.axis_sizes} has {int(np.prod(shape))} devices "
                f"but topology has {len(topo.npus)} NPUs"
            )
        self.registry = registry if registry is not None else default_registry()
        # gateway_strategy/sketch steer the hierarchical inter-pod phase
        # (see repro.core.traffic) — e.g. a CommSketch keeping the
        # data-parallel axis' traffic off a storage plane's uplinks
        self.engine = SynthesisEngine(topo, registry=self.registry,
                                      gateway_strategy=gateway_strategy,
                                      sketch=sketch)
        self._ranks = np.arange(int(np.prod(shape))).reshape(shape)

    def axis_groups(self, axis: str) -> list[list[int]]:
        """Every process group of ``axis``: vary that axis, fix the others."""
        names = list(self.axis_sizes)
        k = names.index(axis)
        moved = np.moveaxis(self._ranks, k, -1)
        return [list(map(int, row)) for row in
                moved.reshape(-1, self.axis_sizes[axis])]

    def spans_pods(self, axis: str) -> bool:
        """True iff this axis' process groups cross a pod boundary (and will
        therefore take the hierarchical synthesis path by default)."""
        if self.topo.partition is None:
            return False
        return self.engine.hierarchical().spans_pods(self.axis_groups(axis)[0])

    def hierarchy_levels(self) -> int:
        """Routing depth of the fabric: 1 = flat, 2 = pods, 3 = pods-of-pods
        (rack -> pod -> plane), i.e. ``partition_depth + 1``. Pod-spanning
        groups synthesize through that many phase levels."""
        return self.topo.partition_depth + 1

    def algorithm(self, kind, axis: str, group_index: int = 0, *,
                  nbytes: float = 1.0, ids=None):
        """The synthesized (or registry-served) algorithm for one group.

        ``kind`` is either a collective name, asked for at ``nbytes`` with
        every other field at its default, or a
        :class:`repro.core.request.CollectiveRequest` (its ``group`` is
        filled in from the axis; other fields pass through, and ``nbytes``
        is not read).

        ``all_gather``/``all_to_all``/``reduce_scatter``/``all_reduce``
        groups that span pods route through the hierarchical pipeline
        automatically; a request with ``hierarchy="never"`` (or "always")
        overrides that."""
        from repro.core.request import CollectiveRequest

        group = self.axis_groups(axis)[group_index]
        if isinstance(kind, CollectiveRequest):
            req = kind.with_group(group)
        else:
            req = CollectiveRequest(kind, group=tuple(group), bytes=nbytes)
        return self.engine.collective(req, ids=ids)

    def joint(self, parts, *, name: str = "pccl_joint"):
        """Jointly synthesize several mesh-axis collectives over one shared
        TEN (paper §6.4): ``parts`` is a list of ``(kind, axis, group_index)``
        or ``(kind, axis, group_index, nbytes)``. Chunk ids are drawn from
        one ``ChunkIds.split()`` family, so the condition builders cannot
        collide — previously every caller had to hand-thread one allocator.

        Only non-reduction kinds are supported (reductions synthesize via a
        reversed topology and cannot share this TEN).
        """
        from repro.core import conditions as cnd
        from repro.core.conditions import ChunkIds

        builders = {"all_gather": cnd.all_gather, "all_to_all": cnd.all_to_all}
        norm = [(p if len(p) == 4 else (*p, 1.0)) for p in parts]
        ids = ChunkIds()
        groups = []
        for child, (kind, axis, group_index, nbytes) in zip(
                ids.split(len(norm)), norm):
            builder = builders.get(kind)
            if builder is None:
                raise ValueError(
                    f"joint synthesis supports {sorted(builders)}, "
                    f"got {kind!r}"
                )
            group = self.axis_groups(axis)[group_index]
            conds = builder(group, ids=child, bytes=nbytes)
            groups.append((f"{kind}_{axis}{group_index}", conds))
        return self.engine.synthesize_joint(groups, name=name)

    def warm(self, kinds=("all_gather", "reduce_scatter"), *,
             nbytes: float = 1.0) -> dict:
        """Pre-populate the registry for every axis/kind; returns stats.

        Thanks to canonicalization this costs one cold synthesis per
        (axis, kind) — the remaining rows are cache hits."""
        for axis in self.axis_sizes:
            for kind in kinds:
                for i in range(len(self.axis_groups(axis))):
                    self.algorithm(kind, axis, i, nbytes=nbytes)
        return self.registry.stats.as_dict()

    def program(self, kind, axis: str, group_index: int = 0, *,
                nbytes: float = 1.0,
                device_of_npu: dict[int, int] | None = None):
        """(PpermuteProgram, BufferPlan) for executing one group's collective
        inside shard_map — synthesis, translation, and buffer planning all
        cached by fingerprint (see repro.comms).

        ``kind`` is a collective name or a
        :class:`repro.core.request.CollectiveRequest` (group filled in from
        the axis), mirroring :meth:`algorithm` — requests execute any engine
        route (hierarchy, TE gateways, sketches, pipelining). A name is
        asked for at ``nbytes``, one member's chunk; an all-reduce is
        pipelined, and its shard split in two halves of ``nbytes / 2``
        when ``nbytes`` exceeds ``SPLIT_ALL_REDUCE_MIB``. Counts
        ``all_reduce.split_shards`` once per call whose program holds two
        chunks a member."""
        from repro.comms.primitives import synthesize_program
        from repro.core.request import CollectiveRequest

        group = tuple(self.axis_groups(axis)[group_index])
        if isinstance(kind, CollectiveRequest):
            req = kind.with_group(group)
        else:
            split = kind == "all_reduce" and nbytes > SPLIT_ALL_REDUCE_MIB
            req = CollectiveRequest(kind, group, bytes=nbytes / (1 + split),
                                    chunks=1 + split,
                                    pipelined=kind == "all_reduce")
        prog, plan = synthesize_program(
            self.topo, req, registry=self.registry,
            device_of_npu=device_of_npu,
        )
        if req.kind == "all_reduce" and len(prog.chunk_holders) == 2 * len(
                group):
            count("all_reduce.split_shards")
        return prog, plan
