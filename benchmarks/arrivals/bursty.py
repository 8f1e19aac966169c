"""Open loop with clustered arrivals: ``open_loop`` with gamma gaps in
place of exponential ones. A gamma gap of shape ``1 / cv**2`` and mean 1
has the coefficient of variation ``gap_cv`` (1 is ``open_loop``'s
Poisson process; BurstGPT, arXiv:2401.17644, reads well above 1 in
production traces): most gaps are near zero and a few are long, so
requests come in bursts at the same mean ``rate_rps``. Everything else,
the ramp, the lengths, the permutation of length pairs among the slots
due in the window, and the sending, is ``open_loop``'s own code."""

from benchmarks import loadgen
from benchmarks.arrivals import open_loop

offer = open_loop.offer


class _GammaGaps:
    """The schedule's generator with its one draw of gaps changed:
    ``open_loop.build`` asks it for ``exponential(mean, n)`` gaps and
    gets gamma gaps of that mean; every other draw is the generator's."""

    def __init__(self, rng, cv: float):
        self._rng, self._shape = rng, 1.0 / cv**2

    def exponential(self, mean: float, n: int):
        return self._rng.gamma(self._shape, mean / self._shape, n)

    def __getattr__(self, name):
        return getattr(self._rng, name)


def build(traffic: dict, fixed, mixed, seconds: float,
          rate: float | None = None) -> list[loadgen.Request]:
    return open_loop.build(
        traffic, _GammaGaps(fixed, traffic["gap_cv"]), mixed, seconds, rate
    )
