"""The systems a configuration serves, and the tickets a round submits.

A configuration lists its ``systems``: each names an operator
(``operator.kind``), a right-hand-side protocol (``rhs.kind``), the
solve method and op-amp, and its ``share`` of every round.  The kinds
are found by name: ``bench/operators/<kind>.py`` defines
``build(spec) -> A`` and ``bench/rhs/<kind>.py`` defines
``draw(rng, a, spec, count) -> (count, n)``.  A new operator or
protocol is a new file there, never an edit of this one.

Every seed draws the same sizes and the same number of tickets of each
system per round; only the right-hand sides' values and the order of
submission change with it.  The program receives only the arrays.
"""

from __future__ import annotations

import dataclasses
import importlib

import numpy as np


@dataclasses.dataclass
class System:
    """One operator of a configuration and how its tickets are made."""

    index: int
    a: np.ndarray                    # float64 operator, siemens
    rhs: dict
    method: str
    opamp: str
    share: int


def operator(spec: dict) -> np.ndarray:
    """The operator ``A`` (float64, siemens) that ``spec`` describes."""
    module = importlib.import_module(f"bench.operators.{spec['kind']}")
    return module.build(spec)


def draw_rhs(rng: np.random.Generator, a: np.ndarray, spec: dict,
             count: int) -> np.ndarray:
    """``count`` right-hand sides for ``A`` by the protocol ``spec``."""
    module = importlib.import_module(f"bench.rhs.{spec['kind']}")
    return module.draw(rng, a, spec, count)


def load_systems(config: dict, rehearse: bool = False) -> list[System]:
    """The configuration's systems; a rehearsal applies each system's
    ``rehearsal`` sizes to its operator."""
    out = []
    for k, spec in enumerate(config["systems"]):
        op = dict(spec["operator"])
        if rehearse:
            op.update(spec.get("rehearsal", {}))
        out.append(System(index=k, a=operator(op), rhs=spec["rhs"],
                          method=spec["method"], opamp=spec["opamp"],
                          share=int(spec.get("share", 1))))
    return out


def round_counts(systems: list[System], round_tickets: int) -> list[int]:
    """Tickets of each system in one round, in proportion to the shares."""
    total = sum(s.share for s in systems)
    if round_tickets % total:
        raise ValueError(f"a round of {round_tickets} tickets does not split "
                         f"into shares summing to {total}")
    return [round_tickets // total * s.share for s in systems]


class TicketStream:
    """The rounds of a run, drawn in order from a seed.

    Each system draws its right-hand sides from a generator of its own,
    so adding a system to a configuration leaves the others' draws as
    they were; one more generator orders each round's submissions.
    """

    def __init__(self, systems: list[System], round_tickets: int, seed):
        self.systems = systems
        self.counts = round_counts(systems, round_tickets)
        base = list(np.atleast_1d(seed))
        self.rngs = [np.random.default_rng(base + [0, s.index]) for s in systems]
        self.order = np.random.default_rng(base + [1])

    def next_round(self) -> list[tuple[System, np.ndarray]]:
        """One round's tickets ``(system, b)``, in submission order."""
        tickets = []
        for system, rng, count in zip(self.systems, self.rngs, self.counts):
            bs = draw_rhs(rng, system.a, system.rhs, count)
            tickets += [(system, b) for b in bs]
        if len(self.systems) > 1:
            tickets = [tickets[k] for k in self.order.permutation(len(tickets))]
        return tickets
