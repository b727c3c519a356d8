"""Both cells cut to a size the CPU holds, for the tests: the same
drivers, generators, reference and comparison, a few layers of width 64."""

from __future__ import annotations

import copy

from yardstick import harness

_load_cell = harness.load_cell  # the tests patch the module's name

TRAIN, SERVE = "gpt2-medium.train.seq1024", "gpt2-xl.serve.backlog"

_SIZES = dict(n_embd=64, n_head=4, n_inner=256, n_layer=2,
              n_positions=64, vocab_size=500)

#: roomy limits for the tiny cells: the tests ask whether a sound run
#: passes and a broken one fails, not where the chip's limits lie
LIMITS = {
    TRAIN: {"loss_step2": 4e-4, "first_grad_norm": 0.05,
            "change_norm": 0.05},
    SERVE: {"served_logit_gap_mean": 0.005},
}


def cell(name: str, dtype: str = "bfloat16") -> harness.Cell:
    c = copy.deepcopy(_load_cell(name))
    c.config.update(_SIZES)
    c.config["departures_forced_by_the_program"]["vocab_size"]["run"] = 512
    c.config["deployment"]["compute_dtype"] = dtype
    if name == TRAIN:
        c.traffic.update(batch=4, seq=64, vocab_below=500)
    else:
        c.config["deployment"].update(slots=4, block_size=8, num_blocks=33,
                                      prefill_chunk=8)
        c.traffic.update(
            requests=24, vocab_below=500, sizes=8, checked_requests=4,
            prompt={"mean": 13, "sigma": 0.5, "min": 4, "max": 30},
            output={"mean": 11, "sigma": 0.4, "min": 4, "max": 24})
    return c
