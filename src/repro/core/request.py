"""CollectiveRequest: one frozen value object per collective call.

A request is the one way to ask for a collective. It carries every tuning
knob (``bytes``, ``chunks``, ``root``, ``hierarchy``, ``pipelined``) and
the two engine-level settings that a call may override
(``gateway_strategy``, ``sketch``), and it is validated at construction so
every layer below can trust it:

* ``SynthesisEngine.collective(request)`` synthesizes it;
  ``MeshCollectivePlanner.algorithm``/``program``,
  ``PlanService.plan`` and ``repro.comms.synthesize_program`` accept the
  same object.
* The registry's route params derive from the request
  (:meth:`CollectiveRequest.registry_params`), in the tuple layout that
  on-disk cache entries were written under, so they keep serving.
* ``ids=`` (the caller's chunk-id allocator) stays a call-site argument —
  it is identity-bearing mutable state, not a description of the
  collective, so it never belongs in the frozen request.

``chunks`` is the per-NPU chunk count for the gather/reduce-scatter family,
the per-pair count for all_to_all, and for all_reduce the pieces (1 or 2)
each member's shard is split into, every piece of ``bytes``.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass, replace

__all__ = [
    "COLLECTIVE_KINDS",
    "CollectiveRequest",
]

COLLECTIVE_KINDS = (
    "all_gather", "all_to_all", "reduce", "reduce_scatter", "all_reduce",
)


@dataclass(frozen=True)
class CollectiveRequest:
    """A complete, immutable description of one collective synthesis.

    ``group`` may be left empty when a layer upstream fills it in (e.g.
    ``MeshCollectivePlanner`` deriving it from a mesh axis) — see
    :meth:`with_group`. ``gateway_strategy``/``sketch`` of ``None`` mean
    "inherit the engine's configuration"; setting either makes the engine
    synthesize through a variant configured accordingly.
    """

    kind: str
    group: tuple = ()
    bytes: float = 1.0
    chunks: int = 1  # per-NPU (gathers) / per-pair (all_to_all) / per shard
    root: int | None = None  # reduce only
    hierarchy: str = "auto"
    pipelined: bool = False  # all_reduce flat route only
    gateway_strategy: str | None = None  # None = engine default
    sketch: object | None = None  # CommSketch | None; None = engine default

    def __post_init__(self):
        if self.kind not in COLLECTIVE_KINDS:
            raise ValueError(
                f"kind={self.kind!r} not in {COLLECTIVE_KINDS}")
        object.__setattr__(
            self, "group", tuple(int(n) for n in self.group))
        object.__setattr__(self, "bytes", float(self.bytes))
        object.__setattr__(self, "chunks", int(self.chunks))
        if self.bytes <= 0.0:
            raise ValueError(f"bytes={self.bytes} must be positive")
        if self.chunks < 1:
            raise ValueError(f"chunks={self.chunks} must be >= 1")
        if self.hierarchy not in ("auto", "always", "never"):
            raise ValueError(
                f"hierarchy={self.hierarchy!r} not in auto/always/never")
        if self.kind == "reduce":
            if self.root is None:
                raise ValueError("reduce needs root=")
            object.__setattr__(self, "root", int(self.root))
            if self.group and self.root not in self.group:
                raise ValueError(
                    f"root {self.root} not in group")
        elif self.root is not None:
            raise ValueError(f"root= only applies to reduce, not {self.kind}")
        if self.kind == "all_reduce" and self.chunks > 2:
            raise ValueError(
                f"chunks={self.chunks}: an all_reduce splits a shard in 1 "
                f"or 2 chunks")
        if self.pipelined and self.kind != "all_reduce":
            raise ValueError(
                f"pipelined= only applies to all_reduce, not {self.kind}")
        if self.sketch is not None and not hasattr(self.sketch, "fingerprint"):
            raise TypeError("sketch must be a CommSketch (needs fingerprint())")

    def with_group(self, group) -> "CollectiveRequest":
        """This request bound to a concrete process group."""
        return replace(self, group=tuple(int(n) for n in group))

    def registry_params(self, route) -> tuple:
        """The registry key's params tuple, in the layout that cached plans
        (in memory and on disk) were keyed under, so they keep serving.

        ``route`` is the resolved hierarchical-route tuple from
        ``SynthesisEngine._route_hierarchical`` (unused for reduce, which
        never routes hierarchically and keys on the root's position)."""
        if self.kind == "reduce":
            return (self.bytes, self.group.index(self.root))
        if self.kind == "all_reduce":
            params = (self.bytes, self.pipelined, route)
            # a split shard keys apart; one chunk keeps the cached layout
            return params if self.chunks == 1 else (*params, self.chunks)
        # all_gather / all_to_all / reduce_scatter
        return (self.bytes, self.chunks, route)

    def fingerprint(self) -> str:
        """Stable identity for memo keys (plan repair records, service
        caches). Not the registry key — the registry canonicalizes groups
        and adds the topology fingerprint itself."""
        sk = self.sketch.fingerprint() if self.sketch is not None else None
        payload = repr((self.kind, self.group, self.bytes, self.chunks,
                        self.root, self.hierarchy, self.pipelined,
                        self.gateway_strategy, sk))
        return hashlib.sha256(payload.encode()).hexdigest()[:16]
