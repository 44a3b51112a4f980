"""The one traffic generator: reads a mix file of parameters, makes requests.

A mix is ``bench/traffic/<name>.json``:

* ``loop``: ``"open"`` (requests due on a schedule, whatever the server
  does) or ``"closed"`` (``clients`` callers, each sending its next
  request when the last one is done, after ``think_s``);
* ``rate_per_s`` (open loop): Poisson arrivals at this mean rate;
* ``steps``: the sampler step counts, drawn in equal shares;
* ``sampler``, ``eta``: the sampler of every request;
* ``labels``: ``null`` (unconditional) or ``"uniform"`` (class labels
  uniform over the configuration's classes); ``guidance``: the
  classifier-free guidance scale of every labelled request (0: none);
* ``warm_batches``: the batch sizes that set-up serves once, so that
  every program and shape the window meets is compiled before it;
* ``tracked``: how many of the window's requests keep their whole
  trajectory for the correctness check.

Every seed gets the same work. An open loop's step counts and gaps are a
fixed multiset (equal shares of ``steps``; the exponential quantiles at
the mix's rate), in one order for every seed: a window holds some tens of
requests, and their order alone moves the tail of an open loop by a third
from seed to seed. The seed draws each request's noise, its label and the
requests the check tracks.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path

import numpy as np

from unet_ref import sub_seed

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Req:
    ordinal: int            # position in the run's request sequence
    due: float              # seconds after the window opens (open loop)
    steps: int
    seed: int               # the request's own noise seed
    y: int | None
    guidance: float


def load(name: str) -> dict:
    path = HERE / "traffic" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} ({path})")
    return json.loads(path.read_text())


class Requests:
    """The run's request sequence, the same for a given (mix, seed)."""

    def __init__(self, mix: dict, seed: int, num_classes: int | None):
        self.mix = mix
        self.rng = np.random.default_rng(sub_seed(seed, "traffic"))
        self.num_classes = num_classes
        if mix["labels"] is not None and not num_classes:
            raise ValueError("a labelled mix needs a class-conditional model")

    def make(self, ordinal: int, due: float, steps: int) -> Req:
        y = None
        if self.mix["labels"] == "uniform":
            y = int(self.rng.integers(0, self.num_classes))
        return Req(ordinal, float(due), int(steps),
                   int(self.rng.integers(0, 2**31 - 1)), y,
                   float(self.mix["guidance"]) if y is not None else 0.0)

    def schedule(self, seconds: float) -> list[Req]:
        """Open loop: every request due in a window of ``seconds``."""
        mix = self.mix
        n = max(1, round(mix["rate_per_s"] * seconds))
        order = np.random.default_rng(sub_seed(0, "schedule"))
        steps = [mix["steps"][k % len(mix["steps"])] for k in range(n)]
        order.shuffle(steps)
        gaps = [-math.log(1 - (k + 0.5) / n) / mix["rate_per_s"]
                for k in range(n)]
        order.shuffle(gaps)
        due = np.cumsum(gaps) - gaps[0]      # the first is due at once
        return [self.make(k, due[k], steps[k]) for k in range(n)]

    def closed(self, ordinal: int, due: float) -> Req:
        """Closed loop: the next request of the sequence."""
        steps = self.mix["steps"]
        return self.make(ordinal, due,
                         steps[int(self.rng.integers(0, len(steps)))])

    def tracked(self, schedule: list[Req] | None) -> set[int]:
        """Ordinals whose trajectories the check compares: drawn from the
        seed, with one of the longest requests among them. An open loop
        draws from requests due in the first 80% of the window, a closed
        loop from its first round (one request from each client, one to each
        batch row), so that each of them finishes."""
        k = self.mix["tracked"]
        if schedule is None:
            pool = list(range(self.mix["clients"]))
            return set(self.rng.choice(pool, size=min(k, len(pool)),
                                       replace=False).tolist())
        horizon = 0.8 * max(r.due for r in schedule) + 1e-9
        pool = [r for r in schedule if r.due <= horizon]
        longest = max(r.steps for r in pool)
        first = self.rng.choice([r.ordinal for r in pool
                                 if r.steps == longest])
        rest = [r.ordinal for r in pool if r.ordinal != first]
        more = self.rng.choice(rest, size=min(k - 1, len(rest)),
                               replace=False).tolist()
        return {int(first), *more}
