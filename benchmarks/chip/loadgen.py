"""One general traffic generator, driven by a mix's data file.

A mix file (``traffic/<mix>.json``) holds only parameters:

- ``loop``: ``"closed"``: ``clients`` callers, each sending its next
  request as soon as the last one's outputs are on the host.
- ``sizes``: ``[[images, weight], ...]``, the request-size mix.
- ``tenants``: ``[[name, weight], ...]``.
- ``pool``: distinct images the requests draw from.
- ``max_wait_ms``, ``max_pending``: the engine's settings for the mix.
- ``source``, ``why``: where the mix comes from and who sends it.

Each caller draws its requests from its own stream of the seed.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    tenant: str
    pool_idx: tuple[int, ...]


class ClosedClient:
    """One closed-loop caller's endless stream of requests."""

    def __init__(self, mix: dict, seed: int, client: int):
        self.mix = mix
        self._rng = np.random.default_rng([seed, 2, client])
        self._sizes = np.array([s for s, _ in mix["sizes"]])
        w = np.array([w for _, w in mix["sizes"]], float)
        self._p_sizes = w / w.sum()
        self._tenants = [t for t, _ in mix["tenants"]]
        w = np.array([w for _, w in mix["tenants"]], float)
        self._p_tenants = w / w.sum()

    def next(self) -> Request:
        size = int(self._rng.choice(self._sizes, p=self._p_sizes))
        tenant = self._tenants[self._rng.choice(len(self._tenants),
                                                p=self._p_tenants)]
        idx = tuple(int(i) for i in
                    self._rng.integers(0, self.mix["pool"], size))
        return Request(tenant, idx)


def warm_requests(mix: dict, seed: int, per_size: int = 2) -> list[Request]:
    """A short burst that sends every request size of the mix from
    every tenant, for set-up."""
    rng = np.random.default_rng([seed, 3])
    out = []
    for size, _ in mix["sizes"]:
        for tenant, _ in mix["tenants"]:
            for _ in range(per_size):
                idx = tuple(int(i) for i in
                            rng.integers(0, mix["pool"], size))
                out.append(Request(tenant, idx))
    return out
