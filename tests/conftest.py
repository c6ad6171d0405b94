"""Shared fixtures."""

import importlib.util
from pathlib import Path

import pytest


@pytest.fixture(scope="session")
def smoke():
    """``chip_smoke.py`` (repo root) as a module; importing it touches no
    device."""
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
