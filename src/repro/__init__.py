"""Massively data-parallel stencil framework (CaCUDA on TPU) + LM stack."""


def __getattr__(name):
    # `from repro import api` without importing jax-heavy modules at
    # package import (repro.api pulls in the sim/cfd stacks)
    if name == "api":
        import importlib

        return importlib.import_module("repro.api")
    raise AttributeError(f"module 'repro' has no attribute {name!r}")
