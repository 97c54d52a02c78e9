"""The control of ``correct`` at a size a test run holds.

On the chip the control (the float32 reference with float8 weights in the
program's place) is read at each cell's own size over many seeds, and the
cell's limit is set between the program's readings and the control's.
Here the same readings are taken on the CPU at the registry's smoke sizes:
on every seed the control reads far wider gaps than the program does.
"""

import time

import pytest

import _paths
from harness import correct, serve

MIX = {"arrivals": "poisson",
       "prompt": {"dist": "uniform", "min": 6, "max": 40},
       "output": {"dist": "uniform", "min": 16, "max": 40}}


@pytest.mark.parametrize("name", ["internlm2-1.8b.float.code",
                                  "phi3-mini-3.8b.float.longgen"])
def test_control_reads_wider_gaps_than_the_program(name):
    cell = _paths.smoke_cell(name, traffic=MIX, rate_per_s=120.0,
                             max_batch=4, page_size=4, max_seq_len=96)
    program, control = [], []
    for seed in (1, 2, 2**31 + 9):
        served, params = serve.serve(cell, seed, 0.1,
                                     t_proc=time.perf_counter())
        read = correct.readings(cell, params, seed, served, control=True)
        assert read["tokens"] >= 300
        program.append(read["max_logit_gap"])
        control.append(read["control_gap"])
    assert min(control) > 2 * max(program), (program, control)
