"""A CPU rehearsal of the admission rule on the closed-loop cells' own
traffic (ISSUE 43): the scheduler's loop with everything but the shapes
taken away. A pass admits (``GenerationConfig.admission_choice``, or the
arrival order the parent admitted in), one prefill pads its batch to a
warmed (P, L), one decode step serves every live slot, and a caller whose
request ended sends its next. Callers, slots, lengths, rungs and batches
are read from ``benchmarks/traffic/*.json``; nothing else goes in.

Arrival order reads the padding the chip measured before the rule
(``programs.prefill_pad_pct.tput``: 27.39 / 37.24 / 14.67, ledger, PR 42),
so the model of where the padding comes from is right, and the rule's
readings (padding, and the share of admissions that went ahead of an
earlier arrival) hold the next change to admission to a number."""
import json
import os
import sys
from collections import deque

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import traffic as tlib  # noqa: E402
from deeplearning4j_tpu.serving import GenerationConfig  # noqa: E402


def arrival_order(cfg, rungs, waiting, free):
    return list(range(min(waiting, free, cfg.prefill_batches[-1])))


def the_rule(cfg, rungs, waiting, free):
    return cfg.admission_choice(rungs, waiting, free)


def rehearse(tr, choose, seed=1, passes=3000, warm=500):
    """(share of the prefill programs' positions that are padding, share
    of the admissions that went ahead of an earlier arrival), in percent,
    over ``passes`` passes of the loop, the first ``warm`` left out."""
    cfg = GenerationConfig(**tr["engine"])
    rng = np.random.default_rng(seed)
    pairs = tlib.stratified_pairs(tr["lengths"], tr["block"])

    def requests():
        while True:       # block by block, in an order the seed draws
            for i in rng.permutation(tr["block"]):
                yield pairs[i]
    nxt = requests()
    queue = deque(next(nxt) for _ in range(tr["callers"]))
    live, free = [], cfg.decode_slots      # tokens each live slot still owes
    tokens = padded = admitted = jumped = 0
    for k in range(passes):
        if queue and free:
            took = choose(cfg, [cfg.prompt_rung(p) for p, _ in queue],
                          len(queue), free)
            cands = [queue[i] for i in took]
            for i in reversed(took):
                del queue[i]
            if k >= warm:
                admitted += len(took)
                jumped += sum(i > n for n, i in enumerate(took))
                tokens += sum(p for p, _ in cands)
                padded += cfg.prefill_rung(len(cands)) * \
                    cfg.prompt_rung(max(p for p, _ in cands))
            # the prefill gives every row its first token
            live += [n - 1 for _, n in cands]
            free -= len(cands)
        # one decode step (a request of one token ended with its prefill)
        ended = len(live)
        live = [n - 1 for n in live if n > 1]
        ended -= len(live)
        free += ended
        queue.extend(next(nxt) for _ in range(ended))
    return 100.0 * (1.0 - tokens / padded), 100.0 * jumped / admitted


@pytest.mark.parametrize("traffic,choose,want,within,jumped", [
    ("serve-longprompt", arrival_order, 27.39, 0.5, 0.0),
    ("serve-extract", arrival_order, 37.24, 0.5, 0.0),
    ("serve-longdoc", arrival_order, 14.67, 0.5, 0.0),
    # the share that jumped is what the chip read under the rule
    # (``sched.admit_jumped_pct.tput`` 30.7-31.5 / 14.6-15.3 / 0.0, PR 43)
    ("serve-longprompt", the_rule, 16.1, 1.0, 31.0),
    ("serve-extract", the_rule, 25.2, 1.0, 15.5),
    ("serve-longdoc", the_rule, 14.6, 1.0, 0.0)])
def test_padding_of_the_closed_loop_cells_traffic(traffic, choose, want,
                                                  within, jumped):
    tr = json.load(open(os.path.join(ROOT, "benchmarks", "traffic",
                                     traffic + ".json")))
    assert tr["kind"] == "closed_loop"
    padding, went_ahead = rehearse(tr, choose)
    assert padding == pytest.approx(want, abs=within)
    assert went_ahead == pytest.approx(jumped, abs=1.5 if jumped else 0.0)
