"""LEO core: cross-backend stall root-cause analysis via backward slicing.

The public API has four layers (see ``docs/api.md`` for a tour):

**Service** — the serving-grade entry point: typed ``AnalyzeRequest`` in,
serializable ``Diagnosis`` out, bounded LRU + on-disk caches, concurrent
multi-backend fan-out over a thread pool::

    from repro.core import AnalyzeRequest, LeoService
    svc = LeoService(cache_dir=".leo_cache")
    diag = svc.diagnose(hlo_text, backend="tpu_v5e")       # Diagnosis
    diag.to_json(); diag.to_markdown(); diag.to_llm_context("C+L(S)")
    svc.submit(AnalyzeRequest(hlo_text=hlo_text))          # queue shape

**Sessions** — the cached facade underneath (raw ``LeoAnalysis`` out).
Parses each HLO text once (content-hash cache), builds each (module,
backend) dependency graph once, and memoizes whole analyses; thread-safe
with single-flight cache fills::

    from repro.core import LeoSession
    session = LeoSession()
    an = session.analyze(hlo_text, backend="tpu_v5e")      # LeoAnalysis
    per_vendor = session.compare_backends(hlo_text)        # parses ONCE

**Backends** — a pluggable registry of vendor descriptors (hardware model +
native stall taxonomy + sync-semantics knobs).  Six ship by default: three
TPU generations and NVIDIA/AMD/Intel-class parts; third parties add more
without touching core files::

    from repro.core import Backend, get_backend, list_backends, register_backend
    register_backend(Backend(name="my_asic", vendor="acme", hw=..., ...))

**Pipeline** — the named, reorderable analysis passes behind every entry
point (sample -> depgraph -> coverage -> sync_edges -> prune -> blame ->
chains -> cct).  Derive variants to insert/remove/replace passes::

    from repro.core import default_pipeline
    pipe = default_pipeline().without("cct")
    ctx = pipe.run(module, "nvidia_gh200")     # raw AnalysisContext
    # (pipe.analyze() needs every LeoAnalysis artifact, so trimmed
    #  pipelines are consumed via run(); the full default supports both)

Legacy one-shot helpers (``analyze_hlo`` / ``analyze_module`` /
``cross_backend_analyze``) remain as thin shims over the same pipeline.
"""
from .analyzer import (
    LeoAnalysis,
    analyze_hlo,
    analyze_module,
    cross_backend_analyze,
)
from .caching import DiskCache, LRUCache
from .backends import (
    Backend,
    BackendRegistry,
    DEFAULT_SYNC_MODEL,
    REGISTRY,
    SyncModel,
    SyncPressureReport,
    SyncResourcePool,
    SyncScoreboard,
    SyncSemantics,
    UnknownBackendError,
    backend_for_device_kind,
    get_backend,
    list_backends,
    register_backend,
    resolve_backend,
    resolve_sync_model,
)
from .blame import (
    BlameResult,
    SchedulerContentionBlame,
    SyncResourceBlame,
    attribute_blame,
)
from .cct import build_cct, format_hot_path
from .collectives import (
    collective_operand_bytes,
    collective_summary,
    total_collective_bytes,
)
from .coverage import single_dependency_coverage
from .depgraph import DependencyGraph, Edge, build_dependency_graph
from .hlo_parser import HloParser, parse_hlo
from .hwmodel import (
    HARDWARE_MODELS,
    SINGLE_ISSUE,
    SINGLE_WAVE,
    TPU_V4,
    TPU_V5E,
    TPU_V5P,
    HardwareModel,
    IssueModel,
    OccupancyModel,
    get_hardware_model,
)
from .isa import (
    Computation,
    EdgeKind,
    Instruction,
    Module,
    OpClass,
    ShapeInfo,
    StallClass,
    SyncKind,
)
from .jaxpr_frontend import from_function, from_jaxpr
from .passes import (
    AnalysisContext,
    AnalysisPass,
    DEFAULT_PIPELINE,
    IncompletePipelineError,
    Pipeline,
    PipelineOrderError,
    default_pipeline,
)
from .pruning import prune
from .report import (
    ADVICE_NOT_RECORDED,
    MIN_SCHEMA_VERSION,
    SCHEMA_VERSION,
    Diagnosis,
    Recommendation,
    diagnostic_context,
    recommendations,
    save_json,
    structured_report,
)
from .roofline import RooflineReport, compute_roofline
from .sampler import (
    IssuePressureReport,
    StallProfile,
    VirtualSampler,
    sample,
)
from .service import AnalyzeRequest, DiagnoseOptions, LeoService
from .session import LeoSession, SessionStats
from .slicing import StallChain, top_chains
from .sync_trace import add_sync_edges

__all__ = [
    # service surface (typed requests / serializable diagnoses)
    "AnalyzeRequest", "DiagnoseOptions", "Diagnosis", "LeoService",
    "Recommendation",
    "ADVICE_NOT_RECORDED", "MIN_SCHEMA_VERSION", "SCHEMA_VERSION",
    # cache tiers
    "DiskCache", "LRUCache",
    # session facade
    "LeoSession", "SessionStats",
    # backend registry + sync resources + issue model
    "Backend", "BackendRegistry", "DEFAULT_SYNC_MODEL", "REGISTRY",
    "IssueModel", "IssuePressureReport", "SINGLE_ISSUE",
    "OccupancyModel", "SINGLE_WAVE",
    "SchedulerContentionBlame",
    "SyncModel", "SyncPressureReport", "SyncResourceBlame",
    "SyncResourcePool", "SyncScoreboard", "SyncSemantics",
    "UnknownBackendError", "backend_for_device_kind", "get_backend",
    "list_backends",
    "register_backend", "resolve_backend", "resolve_sync_model",
    # pass pipeline
    "AnalysisContext", "AnalysisPass", "DEFAULT_PIPELINE",
    "IncompletePipelineError", "Pipeline", "PipelineOrderError",
    "default_pipeline",
    # legacy shims + result object
    "LeoAnalysis", "analyze_hlo", "analyze_module", "cross_backend_analyze",
    # phase primitives
    "BlameResult", "attribute_blame", "build_cct", "format_hot_path",
    "collective_operand_bytes", "collective_summary", "total_collective_bytes",
    "single_dependency_coverage", "DependencyGraph", "Edge",
    "build_dependency_graph", "HloParser", "parse_hlo", "HARDWARE_MODELS",
    "TPU_V4", "TPU_V5E", "TPU_V5P", "HardwareModel", "get_hardware_model",
    "Computation", "EdgeKind", "Instruction", "Module", "OpClass",
    "ShapeInfo", "StallClass", "SyncKind", "from_function", "from_jaxpr",
    "prune", "diagnostic_context", "recommendations", "save_json",
    "structured_report", "RooflineReport", "compute_roofline", "StallProfile",
    "VirtualSampler", "sample", "StallChain", "top_chains", "add_sync_edges",
]
