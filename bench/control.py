"""Readings that set the limits of the comparison that decides ``correct``.

    python3 bench/control.py --workload <name> --side program --seeds 1,2,3 --seconds 5
    python3 bench/control.py --workload <name> --side control --seeds 1,2,3 --seconds 5

``--side program`` runs the cell as a benchmark run does, once per
seed, in one process, and prints the compared numbers of each run: the
lower readings.  ``--side control`` runs the same traffic with the
control in the program's place, which must come out not correct: the
upper readings.

The control is the next precision below what the configuration states:

* the delivered ``x`` is stated in float64, so for a cell without
  settling the control is the reference solve put in the service's
  place and computed in float32 on the device (``jnp.linalg.solve``);
* the settle sweep is stated in float32, and the program has a
  bfloat16 sweep of its own, so for a settle cell the control is the
  service with ``sweep_dtype="bfloat16"``.

The benchmark's own runs never run this.  ``--rehearse`` runs it on the
CPU at the rehearsal sizes, for the tests.
"""

from __future__ import annotations

import copy
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import harness  # noqa: E402


@dataclasses.dataclass
class _Answer:
    x: np.ndarray
    info: dict
    settle_time: float | None = None
    stable: bool = True


class Float32Reference:
    """The reference solve in float32 on the device, behind the
    service's ``submit``/``drain``/``stats`` entry points."""

    def __init__(self, cell, devs):
        self.device = devs[0]
        self.queue: list[tuple[int, np.ndarray, np.ndarray]] = []
        self.batches = 0
        self.next_rid = 0

    def submit(self, a, b, **_opts) -> int:
        self.queue.append((self.next_rid, a, b))
        self.next_rid += 1
        return self.next_rid - 1

    def drain(self) -> dict:
        import jax
        import jax.numpy as jnp

        out = {}
        by_size: dict[int, list] = {}
        for q in self.queue:
            by_size.setdefault(q[2].shape[0], []).append(q)
        for group in by_size.values():
            a = jax.device_put(np.stack([q[1] for q in group]).astype(np.float32),
                               self.device)
            b = jax.device_put(np.stack([q[2] for q in group]).astype(np.float32),
                               self.device)
            x = np.asarray(jnp.linalg.solve(a, b[..., None])[..., 0],
                           dtype=np.float64)
            out.update({q[0]: _Answer(x=x[k], info={}) for k, q in enumerate(group)})
        self.queue.clear()
        self.batches += 1
        return out

    @property
    def stats(self) -> dict:
        return {"host_build_s": 0.0, "device_micro_batches": [self.batches],
                "fallbacks": 0}


def control_cell(cell: harness.Cell) -> tuple[harness.Cell, object]:
    """The cell and the service factory of its control."""
    if cell.traffic.get("submit", {}).get("compute_settling"):
        ctl = copy.deepcopy(cell)
        ctl.traffic["submit"]["sweep_dtype"] = "bfloat16"
        return ctl, harness.default_service
    return cell, Float32Reference


def readings(name: str, side: str, seeds, seconds: float,
             rehearse: bool = False) -> list[dict]:
    """One run per seed on ``side``; each run's ``correct`` and checks."""
    cell = harness.load_cell(name)
    factory = harness.default_service
    if side == "control":
        cell, factory = control_cell(cell)
    out = []
    for seed in seeds:
        t0 = time.perf_counter()
        result = harness.run(cell, seed=seed, seconds=seconds, trace=False,
                             rehearse=rehearse, t_process=t0,
                             service_factory=factory)
        out.append({"side": side, "seed": seed, "correct": result["correct"],
                    "attempted": result["attempted"],
                    "checks": {k: v["value"] for k, v in result["checks"].items()}})
        print(json.dumps(out[-1]), flush=True)
    return out


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--side", choices=("program", "control"), required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    try:
        readings(args.workload, args.side, seeds, args.seconds, args.rehearse)
    except harness.NoChip as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
