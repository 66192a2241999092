"""Declarative scenario registry + SLO auto-tuning for the reproduction.

``load_builtin()`` imports every scenario module so their ``register()``
calls populate :data:`REGISTRY`; the benchmark CLIs, the CI smoke loop
(``python -m repro.scenarios``), the sweep driver, and the catalog
generator all start there.  See ``docs/scenarios.md`` for the authoring
guide and ``docs/CATALOG.md`` for the generated catalog.
"""
from repro.scenarios.registry import (
    REGISTRY,
    SLO,
    DuplicateScenarioError,
    Scenario,
    ScenarioError,
    SLOViolation,
    UnknownKnobError,
    UnknownScenarioError,
    register,
)
from repro.scenarios.runner import (
    ScenarioContext,
    ScenarioResult,
    run_scenario,
)
from repro.scenarios.sweep import (
    EvalPoint,
    KnobAxis,
    ScenarioProblem,
    TuneResult,
)

__all__ = [
    "REGISTRY", "SLO", "Scenario", "ScenarioError", "SLOViolation",
    "DuplicateScenarioError", "UnknownScenarioError", "UnknownKnobError",
    "register", "ScenarioContext", "ScenarioResult", "run_scenario",
    "KnobAxis", "ScenarioProblem", "EvalPoint", "TuneResult",
    "load_builtin",
]

_LOADED = False


def load_builtin() -> None:
    """Import the built-in scenario modules (idempotent)."""
    global _LOADED
    if _LOADED:
        return
    from repro.scenarios import serving  # noqa: F401
    _LOADED = True
