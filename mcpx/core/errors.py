"""Framework exception hierarchy.

The reference has no error hierarchy — it raises bare ``HTTPException(502)``
mid-walk and discards partial results (reference ``control_plane.py:130``,
SURVEY.md bug B5). Here every error carries structure so the API layer can
return partial-failure responses instead of aborting.
"""

from __future__ import annotations

from typing import Any, Optional


class MCPXError(Exception):
    """Base class for all framework errors."""


class RegistryError(MCPXError):
    """Service registry lookup/storage failure."""


class ExecutionError(MCPXError):
    """A DAG execution failed (possibly partially).

    Carries whatever results/errors/trace were accumulated before the failure
    so callers can return a structured partial-failure response rather than
    discarding work (fixes reference bug B5, ``control_plane.py:130``).
    """

    def __init__(
        self,
        message: str,
        *,
        results: Optional[dict[str, Any]] = None,
        errors: Optional[dict[str, str]] = None,
        trace: Any = None,
    ) -> None:
        super().__init__(message)
        self.results = results or {}
        self.errors = errors or {}
        self.trace = trace


class PlannerError(MCPXError):
    """The planner could not produce a valid plan within its retry budget."""


class EngineError(MCPXError):
    """TPU inference-engine failure (compile, OOM, scheduler)."""


class ConfigError(MCPXError):
    """Invalid configuration detected at startup validation."""
