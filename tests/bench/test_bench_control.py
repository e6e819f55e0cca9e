"""The controls, the reference computed with float8 matmul operands (one
step below the bfloat16 operands the program's matmuls take on the TPU)
or in bfloat16, and put in the program's place, come out not correct
through the same checks where the program comes out correct: at a tiny
size on the CPU, for dense and 4-bit KV.
(The chip readings at the cell's own size are ``bench/calibrate.py``'s,
recorded in PERF.md with the limits set from them.)"""
import copy
import time

import pytest

import bench_tiny
from harness import cell as C

# At this size the CPU program matches the reference to rounding, and
# either control moves some served token's logit by more.
TINY_LIMITS = {"max_logit_gap": 1e-3}


# A few hundred served tokens, so that some position is close enough to a
# tie for the control's rounding to change the token it puts first.
MIX = copy.deepcopy(bench_tiny.MIX)
MIX["reference_requests"] = 6
MIX["output"] = dict(MIX["output"], median=32, max=96)


@pytest.mark.parametrize("kv_bits,seed,control", [(0, 6, "bf16"),
                                                  (4, 5, "bf16"),
                                                  (0, 6, "fp8")])
def test_control_fails_where_program_passes(monkeypatch, kv_bits, seed,
                                            control):
    monkeypatch.setattr(C, "check_model",
                        lambda spec, model: bench_tiny.program_config())
    config = bench_tiny.config(kv_bits=kv_bits)
    config["correct"] = dict(TINY_LIMITS)
    out = C.run_cell({"name": "tiny.chat", "chips": 1}, config,
                     MIX, 2**33 + seed, 2.0, False,
                     time.perf_counter(), require_tpu=False,
                     log=lambda m: None, controls=(control,))
    assert out["correct"], out["checks"]
    assert not out["controls"][control]["correct"], out["controls"]
    assert list(out)[-1] == "checks"


def test_judge_without_a_sample_is_not_correct():
    ok, checks = C.judge(None, {"max_logit_gap": 1.0}, 0)
    assert not ok and checks["max_logit_gap"]["value"] == float("inf")
    ok, checks = C.judge({"max_logit_gap": 0.5}, {"max_logit_gap": 1.0}, 1)
    assert not ok and checks["compiles_in_window"]["value"] == 1
    assert C.judge({"max_logit_gap": 0.5}, {"max_logit_gap": 1.0}, 0)[0]
