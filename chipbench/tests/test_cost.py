"""The roofline count against hand arithmetic, for both configurations."""

import json
from pathlib import Path

import cost

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def system(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["system"]


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_telemetry_d64_by_hand():
    sy = system("telemetry-d64")
    assert cost.ell(sy) == 8 and cost.ring_cap(sy) == 36
    # one sketch: buf 16x64 f32 + ring 36x64 f32 + 36 x (4 + 4 + 1) + 7 x 4
    one = 16 * 64 * 4 + 36 * 64 * 4 + 36 * 9 + 28
    assert one == 13_664
    assert cost.state_bytes(sy) == 27_328
    flops, nbytes = cost.update_cost(sy, rows=2048, streams=256)
    assert flops == 2048 * 2 * 64 * 8 == 2_097_152
    assert nbytes == 2 * 27_328 * 256 + 2048 * 64 * 4 == 14_516_224
    least, bound = cost.least_seconds(sy, 2048, 256, PEAKS)
    assert bound == "bytes"
    assert abs(least - 14_516_224 / 819e9) < 1e-15


def test_synthetic_d300_by_hand():
    sy = system("synthetic-d300")
    assert cost.ell(sy) == 32 and cost.ring_cap(sy) == 132
    one = 64 * 300 * 4 + 132 * 300 * 4 + 132 * 9 + 28
    assert one == 236_416
    assert cost.state_bytes(sy) == 472_832
    flops, nbytes = cost.update_cost(sy, rows=512, streams=64)
    assert flops == 512 * 2 * 300 * 32 == 9_830_400
    assert nbytes == 2 * 472_832 * 64 + 512 * 300 * 4 == 61_136_896
    least, bound = cost.least_seconds(sy, 512, 64, PEAKS)
    assert bound == "bytes"
    assert abs(least - 61_136_896 / 819e9) < 1e-15
