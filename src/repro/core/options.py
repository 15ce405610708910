"""Typed options for the triangle-counting front door.

``CountOptions`` consolidates every tuning knob that used to be scattered as
free-function kwargs (``algorithm``, ``variant``, ``backend``, ``interpret``,
``strategy``, ``widths``, ``block``, ``permute``, ``bitmap_bits``) into one
frozen, validated, hashable dataclass. The engine's process-wide executable
cache is keyed by fields derived from these options (see
``docs/ARCHITECTURE.md`` §Executable-cache keying rules), so *equal options
imply equal cache keys*: two ``TriangleCounter`` sessions built from equal
``CountOptions`` over same-shaped graphs share every compiled executable.

``resolve_interpret`` is the single source of truth for the Pallas
interpret mode. Every entry point takes ``interpret=None``, which resolves
when a plan is built from the platform the plan runs on: the interpreter on
the CPU, the compiled kernel on a TPU. A Pallas lane never runs interpreted
on a TPU: asking for it raises ``KernelPlatformError`` naming the lane and
its kernel, rather than hiding the device behind the interpreter.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

import jax

from repro.graphs.device import (
    DEFAULT_SHAPE_POLICY,
    EDGE_KEY_MODES,
    ShapePolicy,
)

__all__ = [
    "BACKENDS",
    "CHOOSERS",
    "CountOptions",
    "DEFAULT_WIDTHS",
    "KernelPlatformError",
    "PREP_BACKENDS",
    "VARIANTS",
    "resolve_interpret",
]

DEFAULT_WIDTHS: Tuple[int, ...] = (8, 32, 128, 512)

VARIANTS = ("filtered", "full")
BACKENDS = ("jnp", "pallas", "ref")
PREP_BACKENDS = ("device", "host")
CHOOSERS = ("heuristic", "measured")

# the Pallas kernels each lane's backend="pallas" path runs
_LANE_KERNELS = {
    "matrix": "masked_spgemm_pallas",
    "hash": "hash_probe_counts_pallas",
}
_INTERSECT_KERNELS = ("intersect_counts_pallas / intersect_counts_probe_pallas"
                      " / intersect_counts_bitmap_pallas")


class KernelPlatformError(RuntimeError):
    """A lane's Pallas kernel cannot run as asked on the plan's platform."""

    def __init__(self, lane: str, platform: str):
        self.lane = lane
        self.kernel = _LANE_KERNELS.get(lane, _INTERSECT_KERNELS)
        self.platform = platform
        super().__init__(
            f"lane {lane!r}: Pallas kernel {self.kernel} would run in the "
            f"interpreter on {platform!r}; on a TPU the compiled kernel runs "
            f"(pass interpret=None or False)"
        )


def resolve_interpret(interpret: Optional[bool],
                      platform: Optional[str] = None, *,
                      lane: str = "intersection",
                      backend: str = "pallas") -> bool:
    """The concrete Pallas interpret flag for a plan on ``platform``.

    Args:
      interpret: None (default) chooses from the platform — interpret on the
        CPU, compiled anywhere else; a bool is taken as given.
      platform: the platform the plan runs on; None means JAX's default
        backend, read at call time (plan time), never at import.
      lane / backend: the lane asking and its backend, for the check below.

    Raises:
      KernelPlatformError: ``interpret=True`` for a ``backend="pallas"``
        lane on a TPU.
    """
    platform = platform or jax.default_backend()
    if interpret is None:
        return platform == "cpu"
    if interpret and backend == "pallas" and platform == "tpu":
        raise KernelPlatformError(lane, platform)
    return bool(interpret)


@dataclasses.dataclass(frozen=True)
class CountOptions:
    """Every knob of a triangle count, validated at construction.

    Attributes:
      algorithm: "auto" (cross-lane cost model, see
        ``repro.core.registry.choose_algorithm``) or a registered lane name —
        "intersection" | "matrix" | "subgraph" | "hash" (TRUST-style
        vertex-centric hashing) | "bfs" (level-ordered wedge closure) |
        "edge" (per-edge support / k-truss) | "intersection_distributed" |
        "matrix_distributed".
      chooser: how ``algorithm="auto"`` resolves — "heuristic" (default:
        the hand-written shape rules on
        ``repro.core.registry._default_chooser``) or "measured" (the
        per-device calibration table from ``repro.core.calibrate``:
        feature-binned lane timings, analytically seeded from executable
        pricing when no measurement exists, falling back to the heuristic
        on a table miss). Ignored when ``algorithm`` names a lane.
      variant: intersection lane — "filtered" (forward algorithm, each
        triangle once) or "full" (every directed edge, found 6×).
      backend: "jnp" | "pallas" | "ref" per-kernel execution path.
      interpret: pallas interpret mode; None (default) resolves at plan
        time from the platform (``resolve_interpret``).
      strategy: intersection/subgraph lanes — per-bucket set-intersection
        core: "auto" (documented cost model) or forced "broadcast" |
        "probe" | "bitmap".
      widths: ascending degree-class bucket widths for the
        intersection/subgraph lanes.
      block: matrix lane tile size (int) or "auto" (``choose_block``).
      permute: matrix lane degree-permutation toggle.
      bitmap_bits: optional forced packed-bitmap capacity (multiple of 32)
        for bitmap-strategy buckets; None (default) sizes it from the
        bucket's id range via ``resolve_strategy``.
      prep_backend: where the intersection/subgraph/edge plan stage runs —
        "device" (default: the jitted prep in ``repro.core.prep`` /
        ``repro.graphs.device``) or "host" (the numpy parity path). The
        matrix lane's tile schedule is host-side either way.
      max_peel_iters: edge lane — upper bound on k-truss peel rounds
        (support recompute → filter → re-orient); the peel normally stops
        at the fixpoint long before. Folded into the edge executables'
        cache key, so equal options share cached edge executables and
        unequal peel knobs miss.
      peel_early_exit: edge lane — stop the peel as soon as a round removes
        no edge (the default). False runs exactly ``max_peel_iters`` rounds
        (the fixpoint is stable under further rounds, so the result is
        identical) — a steady-state benchmarking mode. Also part of the
        edge executables' cache key.
      shape_policy: the ``ShapePolicy`` rounding data-dependent prep extents
        into static shape classes; None (default) means
        ``DEFAULT_SHAPE_POLICY`` (pow2 rounding). Part of the cache key:
        same-policy graphs share traced prep stages and counting
        executables, which is what makes ``count_many`` batchable.
      update_batch_size: dynamic lane — how many normalized edge updates one
        device step applies; larger update lists are chunked. The policy
        rounds it to the delta executables' static row extent, so it is part
        of the dynamic lane's shape classes (and of ``key()``).
      recount_interval: dynamic lane — run the full-recount parity oracle
        every this many applied update batches and assert the incremental
        count matches bit-exactly (the drift assertion). 0 disables the
        periodic oracle (``recount()`` stays available on demand).
      key_mode: packed-edge-key capacity mode for the lanes that address
        vertex pairs as ``a * (n + 1) + b`` keys (edge/k-truss, dynamic,
        ``DeviceCSR.from_edges``): "auto" (default) takes the int32 fast
        path while ``fits_int32_pair_keys(n)`` holds and promotes to the
        wide (x64 int64) mode past it; "int32" forces the fast path
        (raising ``GraphTooLargeError`` past the bound); "wide" forces
        int64 keys. See ``repro.graphs.device.resolve_edge_key_mode`` —
        the repo's single capacity checkpoint.
      max_device_bytes: optional per-bucket device-bytes budget for the
        intersection/subgraph/matrix lanes. ``None`` (default) means
        ``engine.default_device_budget()`` — every bucket monolithic on the
        CPU, a share of the device's memory on an accelerator; an int
        budget makes the engine STREAM any
        bucket whose device arrays would exceed it through the same cached
        executables chunk-by-chunk (pow2 chunk rows ⇒ monotone chunk shape
        classes, zero steady-state recompiles), accumulating partial counts
        on host — graceful degradation instead of OOM. Counts are
        bit-identical to the monolithic path.

    Frozen ⇒ hashable: equal options hash equal, and the engine's
    executable-cache keys are functions of these fields, so equal options
    share cached executables. ``key()`` returns the normalized hashable
    tuple (with ``interpret=None`` and ``shape_policy=None`` resolved) used
    wherever options participate in a cache key.
    """

    algorithm: str = "auto"
    chooser: str = "heuristic"
    variant: str = "filtered"
    backend: str = "jnp"
    interpret: Optional[bool] = None
    strategy: str = "auto"
    widths: Tuple[int, ...] = DEFAULT_WIDTHS
    block: Union[int, str] = "auto"
    permute: bool = True
    bitmap_bits: Optional[int] = None
    prep_backend: str = "device"
    shape_policy: Optional[ShapePolicy] = None
    max_peel_iters: int = 1000
    peel_early_exit: bool = True
    update_batch_size: int = 256
    recount_interval: int = 64
    key_mode: str = "auto"
    max_device_bytes: Optional[int] = None

    def __post_init__(self):
        # normalize widths to a tuple of ints so the dataclass stays hashable
        try:
            widths = tuple(int(w) for w in self.widths)
        except TypeError:
            raise ValueError(f"widths must be an iterable of ints, "
                             f"got {self.widths!r}") from None
        object.__setattr__(self, "widths", widths)

        if self.algorithm != "auto":
            from repro.core.registry import available_algorithms
            names = available_algorithms()
            if self.algorithm not in names:
                raise ValueError(
                    f"unknown algorithm {self.algorithm!r}; expected 'auto' "
                    f"or one of {names}"
                )
        if self.chooser not in CHOOSERS:
            raise ValueError(
                f"unknown chooser {self.chooser!r}; expected one of {CHOOSERS}"
            )
        if self.variant not in VARIANTS:
            raise ValueError(
                f"unknown variant {self.variant!r}; expected one of {VARIANTS}"
            )
        if self.backend not in BACKENDS:
            raise ValueError(
                f"unknown backend {self.backend!r}; expected one of {BACKENDS}"
            )
        if self.interpret is not None and not isinstance(self.interpret, bool):
            raise ValueError(
                f"interpret must be None or a bool, got {self.interpret!r}"
            )
        from repro.kernels.intersect.ops import BITMAP_MAX_BITS, STRATEGIES
        if self.strategy != "auto" and self.strategy not in STRATEGIES:
            raise ValueError(
                f"unknown strategy {self.strategy!r}; expected 'auto' or one "
                f"of {STRATEGIES}"
            )
        if not widths or any(w <= 0 for w in widths) or \
                any(a >= b for a, b in zip(widths, widths[1:])):
            raise ValueError(
                f"widths must be non-empty, positive, strictly ascending; "
                f"got {widths}"
            )
        if self.block != "auto":
            if not isinstance(self.block, int) or isinstance(self.block, bool) \
                    or self.block <= 0:
                raise ValueError(
                    f"block must be a positive int or 'auto', got {self.block!r}"
                )
        if not isinstance(self.permute, bool):
            raise ValueError(f"permute must be a bool, got {self.permute!r}")
        if self.bitmap_bits is not None:
            b = self.bitmap_bits
            if not isinstance(b, int) or isinstance(b, bool) or b <= 0 \
                    or b % 32 or b > BITMAP_MAX_BITS:
                raise ValueError(
                    f"bitmap_bits must be a positive multiple of 32 ≤ "
                    f"{BITMAP_MAX_BITS}, got {b!r}"
                )
        if self.prep_backend not in PREP_BACKENDS:
            raise ValueError(
                f"unknown prep_backend {self.prep_backend!r}; expected one "
                f"of {PREP_BACKENDS}"
            )
        if self.shape_policy is not None and \
                not isinstance(self.shape_policy, ShapePolicy):
            raise ValueError(
                f"shape_policy must be None or a ShapePolicy, "
                f"got {self.shape_policy!r}"
            )
        if not isinstance(self.max_peel_iters, int) \
                or isinstance(self.max_peel_iters, bool) \
                or self.max_peel_iters < 1:
            raise ValueError(
                f"max_peel_iters must be a positive int, "
                f"got {self.max_peel_iters!r}"
            )
        if not isinstance(self.peel_early_exit, bool):
            raise ValueError(
                f"peel_early_exit must be a bool, got {self.peel_early_exit!r}"
            )
        if not isinstance(self.update_batch_size, int) \
                or isinstance(self.update_batch_size, bool) \
                or self.update_batch_size < 1:
            raise ValueError(
                f"update_batch_size must be a positive int, "
                f"got {self.update_batch_size!r}"
            )
        if not isinstance(self.recount_interval, int) \
                or isinstance(self.recount_interval, bool) \
                or self.recount_interval < 0:
            raise ValueError(
                f"recount_interval must be a non-negative int (0 disables "
                f"the periodic oracle), got {self.recount_interval!r}"
            )
        if self.key_mode not in EDGE_KEY_MODES:
            raise ValueError(
                f"unknown key_mode {self.key_mode!r}; expected one of "
                f"{EDGE_KEY_MODES}"
            )
        if self.max_device_bytes is not None:
            b = self.max_device_bytes
            if not isinstance(b, int) or isinstance(b, bool) or b < 1:
                raise ValueError(
                    f"max_device_bytes must be None or a positive int, "
                    f"got {b!r}"
                )

    @property
    def resolved_interpret(self) -> bool:
        """The concrete interpret flag on JAX's default backend
        (``resolve_interpret``)."""
        lane = "intersection" if self.algorithm == "auto" else self.algorithm
        return resolve_interpret(self.interpret, lane=lane,
                                 backend=self.backend)

    @property
    def resolved_shape_policy(self) -> ShapePolicy:
        """The concrete ``ShapePolicy`` (``None`` ⇒ ``DEFAULT_SHAPE_POLICY``)."""
        return self.shape_policy if self.shape_policy is not None \
            else DEFAULT_SHAPE_POLICY

    def key(self) -> tuple:
        """Normalized hashable identity: the fields the engine's executable
        cache keys derive from, with ``interpret=None`` and
        ``shape_policy=None`` resolved — so options differing only in
        explicit-vs-default values hash alike."""
        return (
            self.algorithm, self.variant, self.backend,
            self.resolved_interpret, self.strategy, self.widths,
            self.block, self.permute, self.bitmap_bits,
            self.prep_backend, self.resolved_shape_policy.key(),
            self.max_peel_iters, self.peel_early_exit,
            self.update_batch_size, self.recount_interval,
            self.chooser, self.key_mode, self.max_device_bytes,
        )

    def replace(self, **changes) -> "CountOptions":
        """A copy with ``changes`` applied (re-validated)."""
        return dataclasses.replace(self, **changes)

    def plan_kwargs(self, lane: str) -> dict:
        """The ``plan_triangle_count`` kwargs this lane consumes.

        Lanes ignore knobs that do not apply to them (the matrix lane has no
        ``widths``; the intersection lane no ``block``), so one options
        object can drive ``algorithm="auto"`` across all lanes.
        """
        if lane == "intersection":
            return dict(variant=self.variant, backend=self.backend,
                        interpret=self.interpret, widths=self.widths,
                        strategy=self.strategy, bitmap_bits=self.bitmap_bits,
                        prep_backend=self.prep_backend,
                        shape_policy=self.shape_policy,
                        max_device_bytes=self.max_device_bytes)
        if lane == "subgraph":
            return dict(backend=self.backend, interpret=self.interpret,
                        widths=self.widths, strategy=self.strategy,
                        bitmap_bits=self.bitmap_bits,
                        prep_backend=self.prep_backend,
                        shape_policy=self.shape_policy,
                        max_device_bytes=self.max_device_bytes)
        if lane == "matrix":
            return dict(backend=self.backend, interpret=self.interpret,
                        block=self.block, permute=self.permute,
                        max_device_bytes=self.max_device_bytes)
        if lane == "edge":
            return dict(widths=self.widths, strategy=self.strategy,
                        bitmap_bits=self.bitmap_bits,
                        prep_backend=self.prep_backend,
                        shape_policy=self.shape_policy,
                        max_peel_iters=self.max_peel_iters,
                        peel_early_exit=self.peel_early_exit,
                        key_mode=self.key_mode)
        if lane == "dynamic":
            return dict(backend=self.backend, interpret=self.interpret,
                        widths=self.widths, strategy=self.strategy,
                        bitmap_bits=self.bitmap_bits,
                        shape_policy=self.shape_policy,
                        update_batch_size=self.update_batch_size,
                        recount_interval=self.recount_interval,
                        key_mode=self.key_mode)
        if lane == "hash":
            return dict(backend=self.backend, interpret=self.interpret,
                        widths=self.widths,
                        prep_backend=self.prep_backend,
                        shape_policy=self.shape_policy)
        if lane == "bfs":
            return dict(backend=self.backend, interpret=self.interpret,
                        widths=self.widths, strategy=self.strategy,
                        bitmap_bits=self.bitmap_bits,
                        shape_policy=self.shape_policy)
        if lane == "intersection_distributed":
            return dict(variant=self.variant, backend=self.backend,
                        interpret=self.interpret, widths=self.widths,
                        strategy=self.strategy, bitmap_bits=self.bitmap_bits,
                        prep_backend=self.prep_backend,
                        shape_policy=self.shape_policy)
        if lane == "matrix_distributed":
            return dict(backend=self.backend, interpret=self.interpret,
                        block=self.block, permute=self.permute)
        lanes = ("bfs", "dynamic", "edge", "hash", "intersection",
                 "intersection_distributed", "matrix", "matrix_distributed",
                 "subgraph")
        raise ValueError(
            f"unknown engine lane {lane!r}; expected one of {lanes}"
        )
