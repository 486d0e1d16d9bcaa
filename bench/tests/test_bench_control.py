"""The control of the served-token check: the plain reference put in the
program's place one precision step down (float8 operands of every
matrix product for the bfloat16 the configurations state). At every
position of a sequence it picks a token; judged by the float32
reference and the configured limits, those picks must come out not
correct. Here at the published widths and
vocabulary with 8 layers and 256 positions, a size a CPU test holds; on
the chip at the cells' own sizes with ``bench/tools/control.py``."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _paths  # noqa: E402,F401

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench.harness import correct, spec, system, weights  # noqa: E402


@pytest.mark.parametrize("name", ["qwen1.5-0.5b", "granite-3-2b"])
def test_float8_control_is_not_correct(name):
    cfg = spec.load_json(os.path.join(spec.BENCH_DIR, "configs",
                                      name + ".json"))
    cfg.update(num_hidden_layers=8)
    fam, ref_model = spec.load_family(cfg["family"])
    arch = fam.arch_config(cfg)
    params = weights.for_config(system.init_fn(arch), 2 ** 31 + 21, cfg)
    w = fam.plain_weights(params, cfg)
    seq = [int(t) for t in np.random.default_rng(4).integers(
        0, cfg["vocab_size"], 256)]
    gaps = ref_model.token_gaps(w, cfg, seq, 8, 256, control=True)
    g = gaps["control"]
    numbers = {"logit_gap": float(g.max()), "logit_gap_mean": float(g.mean())}
    ok, checks = correct.judge(numbers, cfg["limits"])
    assert checks and not ok, checks
