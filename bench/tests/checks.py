"""Shared by the check tests: one rehearsal run of a cell in-process."""
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402


def rehearse(cell: str, seed: int, seconds: float, **kw) -> dict:
    """One CPU run of ``cell`` at its rehearsal sizes (``config`` and
    ``traffic`` override them); returns the result object."""
    return run.run(["--workload", cell, "--seed", str(seed),
                    "--seconds", str(seconds), "--rehearse"], **kw)


def failed(result: dict) -> list:
    """The compared numbers that exceed their limits."""
    return [k for k, c in result["checks"].items() if c["value"] > c["limit"]]
