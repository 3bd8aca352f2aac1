"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Mirrors the reference's multi-node-without-a-cluster test ladder
(reference: SURVEY.md §4): pure-logic tests + fake accelerators. All sharding
tests run against 8 virtual CPU devices so multi-chip code paths execute
without TPU hardware.
"""

import os

import asyncio
import inspect
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from dynamo_tpu.utils.jaxtools import (  # noqa: E402
    enable_compile_cache,
    force_platform,
)

# Tests never touch a chip: pin the CPU platform (exported, so every
# CLI-e2e child inherits it) with 8 virtual devices.
force_platform("cpu", cpu_devices=8)
# Persistent compile cache, by the ONE rule (utils/jaxtools.py): tier-1
# is dominated by re-jitting the same programs on every run, and every
# CLI-e2e subprocess would recompile them again from scratch. The rule
# exports its choice, so spawned worker processes land on the same
# directory; an externally set JAX_COMPILATION_CACHE_DIR stays in charge.
enable_compile_cache()


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    """Minimal asyncio support: run ``async def`` tests via asyncio.run."""
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        # generous hang-cap: subprocess-spawning tests (supervisor e2e) can
        # take minutes under full-suite CPU contention
        asyncio.run(asyncio.wait_for(fn(**kwargs), timeout=300))
        return True
    return None
