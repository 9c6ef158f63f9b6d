"""Shared environment for tests that spawn python subprocesses.

The subprocess env is minimal on purpose (reproducible child runs).  The
children are CPU runs: ``JAX_PLATFORMS=cpu`` keeps them off any
accelerator, which the test process itself may hold (one process per
chip), and keeps them from probing for one that is absent.
"""
import os


def subprocess_env(**overrides) -> dict:
    env = {
        "PYTHONPATH": "src",
        "PATH": "/usr/bin:/bin",
        "HOME": os.environ.get("HOME", ""),
        "JAX_PLATFORMS": "cpu",
    }
    env.update(overrides)
    return env
