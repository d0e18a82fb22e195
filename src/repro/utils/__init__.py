"""Shared utilities: timing, logging, registries, pytree helpers."""
from repro.utils.timing import Timer
from repro.utils.registry import Registry
from repro.utils.logging import get_logger
from repro.utils import tree

__all__ = ["Timer", "Registry", "get_logger", "tree"]
