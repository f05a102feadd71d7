"""Operations and bytes of the kernels the benchmark puts on a roofline,
and the peaks they are held to (``peaks.json``, keyed by the device's
``device_kind``; a device that is not there is an error)."""
from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def candidate_pass_bytes(n: int, d: int, g: int) -> int:
    """HBM bytes one candidate pass must move, whatever implements it:
    the (N, D) float32 points read once; the new best distance and
    label (N each) and the (N, G) group lower bounds written once."""
    return 4 * (n * d + 2 * n + n * g)


def candidate_pass_flops(d: int, evals: float) -> float:
    """Two operations (multiply, add) per coordinate of each distance
    the pass evaluates."""
    return 2.0 * d * evals


def least_seconds(flops: float, nbytes: float, pk: dict) -> tuple:
    """``(seconds, bound)``: the larger of the float32-at-HIGHEST
    compute time and the HBM time, and which of the two it is."""
    f32_rate = pk["bf16_flops_per_s"] / pk["f32_highest_bf16_passes"]
    t_flops = flops / f32_rate
    t_bytes = nbytes / pk["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
