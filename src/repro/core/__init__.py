"""repro.core — the paper's contribution: MDRQ access paths on modern hardware.

Public API:
  * types: ``RangeQuery``, ``Dataset`` + numpy oracles
  * result specs: ``Ids``, ``Count``, ``Mask``, ``TopK``, ``Agg``
    (``ResultSpec`` protocol + ``register_result_spec`` extension hook)
  * engines: ``MDRQEngine`` (facade/registry), ``build_columnar_scan``,
    ``build_kdtree``, ``build_rstar``, ``build_vafile``, ``DistributedScan``
  * access-path layer: ``AccessPath`` protocol + adapters (``core.paths``)
  * planning: ``Planner``, ``Histograms``, ``CostModel``, ``BatchPlan``
  * mutable plane: ``MutableDelta``, ``DeltaView``, ``Compactor``
    (``MDRQEngine.append`` / ``delete`` / ``compact``)
"""
from repro.core.types import (Agg, Count, Dataset, DeltaHostCtx, Ids, Mask,
                              QueryBatch, RangeQuery, RESULT_MODES,
                              ResultSpec, TopK, match_ids_np, match_mask_np,
                              register_result_spec, resolve_spec,
                              validate_mode)
from repro.core.delta import Compactor, DeltaView, MutableDelta
from repro.core.engine import MDRQEngine, ALL_METHODS, BatchStats
from repro.core.paths import AccessPath, PerQueryPath, PlanInputs
from repro.core.scan import build_columnar_scan
from repro.core.kdtree import build_kdtree
from repro.core.rstar import build_rstar
from repro.core.vafile import build_vafile
from repro.core.planner import (BatchPlan, CalibrationFit, CalibrationReport,
                                CostModel, Histograms, Planner)
from repro.core.distributed import DistributedScan, make_data_mesh

__all__ = [
    "Dataset", "QueryBatch", "RangeQuery", "RESULT_MODES", "match_ids_np",
    "match_mask_np", "validate_mode", "resolve_spec",
    "ResultSpec", "Ids", "Count", "Mask", "TopK", "Agg", "DeltaHostCtx",
    "register_result_spec",
    "MutableDelta", "DeltaView", "Compactor",
    "MDRQEngine", "ALL_METHODS", "BatchStats",
    "AccessPath", "PerQueryPath", "PlanInputs",
    "build_columnar_scan", "build_kdtree", "build_rstar",
    "build_vafile", "BatchPlan", "CalibrationFit", "CalibrationReport",
    "CostModel", "Histograms", "Planner",
    "DistributedScan", "make_data_mesh",
]
