"""The one backlog generator: a traffic mix is data (groups of jobs with
their learning rates, repeats, batch, sequence length and steps), and this turns it and the run's seed into Saturn ``Job``s.

The seed changes the weights and the data of every job and nothing else:
each seed gives the same jobs, in the same order, with the same sizes.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, Dict, List

SEED_SPACE = 2 ** 31 - 1


@dataclasses.dataclass(frozen=True)
class JobSpec:
    name: str
    lr: float
    batch: int
    seq: int
    steps: int
    seed: int


def job_seed(run_seed: int, index: int) -> int:
    """A job's seed from the run's seed and its place in the backlog,
    below 2**31 (it seeds numpy's RandomState and a JAX PRNG key)."""
    h = hashlib.sha256(f"{int(run_seed)}:{int(index)}".encode()).digest()
    return int.from_bytes(h[:8], "little") % SEED_SPACE


def expand(traffic: Dict[str, Any], run_seed: int) -> List[JobSpec]:
    """Every job of the mix: each group is its ``lr`` list times
    ``repeats``, in that order."""
    out: List[JobSpec] = []
    for g in traffic["groups"]:
        for lr in g["lr"]:
            for _ in range(int(g.get("repeats", 1))):
                i = len(out)
                out.append(JobSpec(
                    name=f"j{i:03d}", lr=float(lr), batch=int(g["batch"]),
                    seq=int(g["seq"]), steps=int(g["steps"]),
                    seed=job_seed(run_seed, i)))
    return out


def saturn_jobs(specs: List[JobSpec], model_cfg) -> list:
    from repro.core.job import Job
    return [Job(name=s.name, cfg=model_cfg, batch_size=s.batch,
                seq_len=s.seq, total_steps=s.steps, lr=s.lr, seed=s.seed)
            for s in specs]
