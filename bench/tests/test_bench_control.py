"""Each cell's control, the reference in the program's place at the
precision below the configuration's, must come out not correct."""
from __future__ import annotations

import jax

from bench import harness
from bench.tests.conftest import BIG_SEED, MAMBA_SMALL

SPEC = harness.load_spec()


def test_decode_fp8_control_separates_at_small_size():
    """At a small size the fp8 reference's gap lies far above the bf16
    program's, and the program's below the cell's limit."""
    _, cfg = harness.load_config(SPEC, "mamba2-2.7b")
    mix = harness.load_traffic("decode-b8.replay")
    limit = mix["limits"]["decode_gap"]
    prog = harness.load_program(mix["program"]["kind"])
    orig = prog.build(cfg, MAMBA_SMALL, mix["program"], BIG_SEED,
                      jax.devices()[:1])
    orig.run(24)
    orig.release()
    control = orig.control()["decode_gap"]
    program = orig.check()["decode_gap"]
    assert program < limit
    assert control > 3 * program
