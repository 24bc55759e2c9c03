"""The serving check's sample (``runners.serve.choose_for_check``), replayed
on each serving cell's own traffic without a device: a correct program must
always be able to meet the check's ``min_tokens`` within ``max_requests``.

The replay takes as finished the first N requests of ``loadgen.plan`` for
the cell: N is the fewest requests a 40 s window of that cell finishes, which
its traffic file states with its source as
``"finished_in_window": {"fewest": N, "source": "..."}`` (a closed loop sends
a request for each one it finishes, and the plan's order is the sending
order).
"""

import json
import os

import pytest

from benchmarks.harness import loadgen
from benchmarks.runners import serve

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SEEDS = [2**31 + 104729 * k for k in range(200)]

class _Done:
    """A finished record as the runner keeps it: the plan and every token."""

    __slots__ = ("planned", "tokens", "error")

    def __init__(self, planned):
        self.planned, self.tokens, self.error = planned, [0] * planned.max_new, None


def _load(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


def _serving_cells():
    manifest = _load("BENCHMARK.json")
    files = {c["name"]: c["file"] for c in manifest["configs"]}
    out = []
    for cell in manifest["workloads"]:
        config = _load(files[cell["config"]])
        if config["runner"] == "serve":
            out.append(pytest.param(cell["name"], config,
                                    _load(f"benchmarks/workloads/{cell['traffic']}.json"),
                                    id=cell["name"]))
    return out


def _short_seeds(config, traffic, n_finished):
    check = config["check"]
    vocab = int(config["constructor_kwargs"]["vocab_size"])
    short = []
    for seed in SEEDS:
        planned = loadgen.plan(traffic, seed, 40, vocab)
        done = [_Done(p) for p in planned[:n_finished]]
        greedy, sampled = serve.choose_for_check(done, seed, check)
        tokens = sum(len(r.tokens) for r in greedy)
        # as the runner: sampled requests are counted where the traffic has any
        wanted = int(check.get("sampled_requests", 0)) if any(p.temperature > 0.0 for p in planned) else 0
        if (tokens < int(check["min_tokens"]) or len(greedy) > int(check["max_requests"])
                or len(sampled) < wanted):
            short.append((seed, len(greedy), tokens))
    return short


@pytest.mark.parametrize("name,config,traffic", _serving_cells())
def test_the_check_reaches_its_tokens_on_every_seed(name, config, traffic):
    short = _short_seeds(config, traffic, traffic["finished_in_window"]["fewest"])
    assert not short, f"{len(short)} of {len(SEEDS)} seeds fall short of the check: {short[:5]}"


def test_the_replay_sees_a_check_that_stops_short():
    """Nemotron's check at the ``max_requests`` 6 it had: the longest request
    and one of each of its 6 prefill classes fill the places, and in some
    windows their answers sum to under 4000 tokens."""
    _, config, traffic = next(c.values for c in _serving_cells() if c.id == "nemotron3-reason-closed")
    config = dict(config, check=dict(config["check"], max_requests=6))
    assert len(_short_seeds(config, traffic, traffic["finished_in_window"]["fewest"])) >= 5


def test_the_selection_is_the_runners_own():
    """The longest request, then one of every prefill class, then others in
    the seed's order up to ``min_tokens``; sampled ones drawn apart. The
    indices are what the runner chose before the rule was a function."""
    prompts = [5, 40, 17, 130, 9, 33, 70, 12, 260, 24, 8, 50]
    answers = [7, 3, 12, 4, 9, 15, 6, 11, 2, 8, 10, 5]
    warm = {2, 6, 9}
    done = [_Done(loadgen.Planned(i, 0.0, [1] * prompts[i], answers[i], 0.8 if i in warm else 0.0))
            for i in range(12)]
    greedy, sampled = serve.choose_for_check(
        done, 2**31 + 11, {"min_tokens": 30, "max_requests": 8, "sampled_requests": 2})
    assert [r.planned.index for r in greedy] == [8, 7, 11, 3, 5]
    assert [r.planned.index for r in sampled] == [6, 9]
    greedy, sampled = serve.choose_for_check(done, 2**31 + 11, {"min_tokens": 1000, "max_requests": 7})
    assert [r.planned.index for r in greedy] == [8, 7, 11, 3, 5, 0, 10] and sampled == []
