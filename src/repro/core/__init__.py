"""Core library: the paper's three exact triangle-counting formulations
behind one front door.

Public API:
    TriangleCounter / CountOptions / CountResult — the session facade: one
        typed options bag, one cached plan, cross-lane ``algorithm="auto"``
    CounterSession — the shared session base (``count()`` /
        ``count_with_stats()`` / ``cache_stats()``) both session types
        expose
    DynamicTriangleCounter / DynamicPlan / plan_dynamic_count /
        EdgeUpdate / normalize_edge_updates — the dynamic lane
        (``algorithm="dynamic"``): batched edge updates applied to the
        device-resident CSR in place, incremental exact counts via cached
        delta executables, periodic full-recount parity oracle
    register_algorithm / available_algorithms / choose_algorithm /
        set_auto_chooser — the algorithm registry + the heuristic auto
        cost model
    CalibrationTable / calibrate / analytic_seed / choose_measured /
        install_measured_chooser / save_table / load_table /
        set_default_table — the measured ``algorithm="auto"`` chooser
        (``CountOptions(chooser="measured")``): per-device calibration
        tables built from timed micro-runs, cold-started by HLO/roofline
        pricing, persisted as ``CALIB_<device>.json`` sidecars
    plan_hash_count / plan_bfs_count — direct planners for the two newest
        lanes ("hash": TRUST-style per-vertex hash probing; "bfs":
        level-ordered forward-edge closure over the shared intersection
        executables)
    available_strategies — the valid intersection-strategy names (the
        discovery twin of ``available_algorithms`` /
        ``repro.graphs.available_datasets``)
    plan_triangle_count / TrianglePlan — the plan/execute engine underneath:
        device-resident prep (see ``repro.core.prep``), device buffers +
        cached compiled kernels
    GraphBatch — same-policy graphs stacked into one vmapped device
        dispatch (the ``count_many`` fast path)
    TrussPlan / plan_edge_support — the edge lane (``algorithm="edge"``):
        cached per-edge support executables + the device k-truss peel loop
        (surfaced as ``TriangleCounter.edge_support`` / ``k_truss`` /
        ``truss_decomposition``)
    cache_info / clear_caches / set_cache_limit (+ the original
        executable_cache_info / clear_executable_cache pair) — the
        process-wide executable cache, since PR 8 a thread-safe bounded LRU
        (default 512 entries, ``TC_EXEC_CACHE_SIZE`` env var) with
        hit/miss/eviction counters, shared by every session and the
        ``repro.serve`` front end
    graph_fingerprint — stable CSR content hash; with
        ``CountOptions.key()`` it forms ``CounterSession.session_key()``,
        the serving layer's session-reuse identity
    resolve_interpret / KernelPlatformError — the Pallas interpret mode,
        resolved at plan time from the platform (interpreted on the CPU,
        compiled on a TPU)
    enumerate_triangles — host-side triangle enumeration
    k_truss / edge_support — DEPRECATED shims over the retained numpy parity
        oracle; use the ``TriangleCounter`` methods
    triangle_count_scipy / triangle_count_brute / triangle_count_forward_cpu
        — oracles
    triangle_count_* (+ ``*_distributed``) — DEPRECATED one-shot shims over
        the facade; signatures and return values unchanged
"""

from repro.core.options import (
    CHOOSERS,
    CountOptions,
    DEFAULT_WIDTHS,
    KernelPlatformError,
    resolve_interpret,
)
from repro.core.registry import (
    available_algorithms,
    choose_algorithm,
    register_algorithm,
    set_auto_chooser,
)
from repro.core.engine import (
    DISTRIBUTED_ALGORITHMS,
    STRATEGIES,
    DynamicPlan,
    GraphBatch,
    TrianglePlan,
    TrussPlan,
    cache_info,
    choose_strategy,
    clear_caches,
    clear_executable_cache,
    executable_cache_info,
    mesh_cache_component,
    set_cache_limit,
    plan_bfs_count,
    plan_dynamic_count,
    plan_edge_support,
    plan_hash_count,
    plan_triangle_count,
    resolve_strategy,
)
from repro.core.calibrate import (
    CalibrationTable,
    analytic_seed,
    calibrate,
    choose_measured,
    install_measured_chooser,
    load_table,
    save_table,
    set_default_table,
)
from repro.core.api import (
    CounterSession,
    CountResult,
    DynamicTriangleCounter,
    TriangleCounter,
    graph_fingerprint,
)
from repro.graphs.device import GraphTooLargeError
from repro.graphs.formats import EdgeUpdate, normalize_edge_updates
from repro.kernels.intersect.ops import available_strategies
from repro.core.tc_intersection import (
    triangle_count_intersection,
    prepare_intersection_buckets,
)
from repro.core.tc_matrix import triangle_count_matrix, build_tile_schedule
from repro.core.tc_subgraph import (
    triangle_count_subgraph,
    subgraph_match_triangle,
    peel_to_two_core,
)
from repro.core.listing import (
    enumerate_triangles,
    triangles_per_vertex,
    clustering_coefficients,
    transitivity,
    edge_support,
    k_truss,
)
from repro.core.distributed import (
    triangle_count_matrix_distributed,
    triangle_count_intersection_distributed,
)
from repro.core.oracle import (
    triangle_count_scipy,
    triangle_count_brute,
    triangle_count_forward_cpu,
)

__all__ = [
    "CHOOSERS",
    "CalibrationTable",
    "CountOptions",
    "CountResult",
    "CounterSession",
    "TriangleCounter",
    "DynamicTriangleCounter",
    "DynamicPlan",
    "EdgeUpdate",
    "GraphTooLargeError",
    "normalize_edge_updates",
    "DEFAULT_WIDTHS",
    "KernelPlatformError",
    "resolve_interpret",
    "register_algorithm",
    "available_algorithms",
    "available_strategies",
    "choose_algorithm",
    "set_auto_chooser",
    "DISTRIBUTED_ALGORITHMS",
    "STRATEGIES",
    "GraphBatch",
    "TrianglePlan",
    "TrussPlan",
    "analytic_seed",
    "calibrate",
    "choose_measured",
    "install_measured_chooser",
    "load_table",
    "save_table",
    "set_default_table",
    "plan_bfs_count",
    "plan_dynamic_count",
    "plan_edge_support",
    "plan_hash_count",
    "plan_triangle_count",
    "choose_strategy",
    "resolve_strategy",
    "executable_cache_info",
    "clear_executable_cache",
    "cache_info",
    "clear_caches",
    "mesh_cache_component",
    "set_cache_limit",
    "graph_fingerprint",
    "triangle_count_intersection",
    "prepare_intersection_buckets",
    "triangle_count_matrix",
    "build_tile_schedule",
    "triangle_count_subgraph",
    "subgraph_match_triangle",
    "peel_to_two_core",
    "enumerate_triangles",
    "triangles_per_vertex",
    "clustering_coefficients",
    "transitivity",
    "edge_support",
    "k_truss",
    "triangle_count_matrix_distributed",
    "triangle_count_intersection_distributed",
    "triangle_count_scipy",
    "triangle_count_brute",
    "triangle_count_forward_cpu",
]
