"""The one generator of jobs, driven by a traffic mix's data file.

A mix (``benchmark/traffic/<name>.json``) describes its jobs as fields,
each a constant or a draw:

- an int, a string or a list: a constant (``"0x..."`` strings stay
  strings; the field's user decodes them);
- ``{"uniform": [a, b]}``: a whole number drawn uniformly in [a, b];
- ``{"bytes": n}``: ``n`` random bytes, as hex;
- ``{"step": [start, stride]}``: ``start + k * stride`` for job ``k``;
- ``{"strata": [lo, hi, n]}``: [lo, hi] cut into an even number ``n``
  of equal strata; each block of ``n`` jobs takes every stratum once, at
  a uniform point inside it, in pairs of mirrored strata (``i`` and
  ``n - 1 - i``) whose order is drawn for the block. Every seed so gives
  the same spread of values in another order, and any even count of
  jobs has close to the middle as its mean;
- ``{"list": [n, spec]}``: ``n`` values of ``spec``;
- ``{"join": [spec, ...]}``: the hex strings of the specs, concatenated.

``per_run`` fields are drawn once for the run; ``per_job`` fields for
each job; ``warmup`` is a list of field overrides, one per warm-up job,
laid over a job drawn from a stream of its own. Every draw comes from
``--seed``, the stream and the field's name, so the same seed gives the
same jobs and job ``k`` does not depend on how many jobs ran before it.
"""

from __future__ import annotations

import random
from typing import Any, Dict, List


def _draw(spec: Any, rng: random.Random, k: int, block_rng=None) -> Any:
    if not isinstance(spec, dict):
        return spec
    if len(spec) != 1:
        raise ValueError(f"a drawn field has one key, got {sorted(spec)}")
    (kind, arg), = spec.items()
    if kind == "uniform":
        lo, hi = arg
        return rng.randint(lo, hi)
    if kind == "bytes":
        return rng.randbytes(arg).hex()
    if kind == "step":
        start, stride = arg
        return start + k * stride
    if kind == "strata":
        lo, hi, n = arg
        brng = block_rng(k // n)
        pairs = [(i, n - 1 - i) if brng.random() < 0.5 else (n - 1 - i, i)
                 for i in range(n // 2)]
        brng.shuffle(pairs)
        order = [i for pair in pairs for i in pair]
        width = (hi - lo + 1) / n
        start = lo + int(order[k % n] * width)
        return rng.randint(start, lo + int((order[k % n] + 1) * width) - 1)
    if kind == "list":
        n, inner = arg
        return [_draw(inner, rng, k, block_rng) for _ in range(n)]
    if kind == "join":
        return "".join(_draw(part, rng, k, block_rng) for part in arg)
    raise ValueError(f"unknown draw {kind!r}")


def _fields(specs: Dict[str, Any], seed: int, stream: str, k: int) -> Dict[str, Any]:
    return {
        name: _draw(spec, random.Random(f"{seed}/{stream}/{name}"), k,
                    lambda b, name=name: random.Random(f"{seed}/{name}/block{b}"))
        for name, spec in specs.items()
    }


class Traffic:
    """Jobs of one mix for one seed: plain dicts of fields."""

    def __init__(self, mix: Dict[str, Any], seed: int):
        self.mix = mix
        self.seed = seed
        self.run_fields = _fields(mix.get("per_run", {}), seed, "run", 0)

    def job(self, k: int) -> Dict[str, Any]:
        """Job ``k`` of the measured window."""
        job = dict(self.run_fields)
        job.update(_fields(self.mix.get("per_job", {}), self.seed, f"job{k}", k))
        return job

    def warmup(self) -> List[Dict[str, Any]]:
        jobs = []
        for i, over in enumerate(self.mix.get("warmup", [])):
            job = dict(self.run_fields)
            specs = dict(self.mix.get("per_job", {}), **over)
            job.update(_fields(specs, self.seed, f"warmup{i}", i))
            jobs.append(job)
        return jobs

    def sample(self, n_answered: int) -> List[int]:
        """Which answered jobs the reference recomputes in full: all of
        them, or ``sample`` drawn from the seed."""
        size = self.mix.get("sample", "all")
        if size == "all" or size >= n_answered:
            return list(range(n_answered))
        rng = random.Random(f"{self.seed}/sample")
        return sorted(rng.sample(range(n_answered), size))
