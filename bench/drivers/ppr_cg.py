"""Personalized PageRank by conjugate gradients through ``GraphServer.serve``.

The closed loop of :mod:`bench.drivers.cg` on the configuration's Graph500
Kronecker graph (:mod:`bench.gen.kronecker`): the matrix is PageRank's
symmetric form ``I − α D^{-1/2} W D^{-1/2}``, and each set of
``iterations_per_set`` iterations answers one personalized query from
``b = (1 − α) D^{-1/2} s``, with ``s`` uniform over ``sources_per_query``
non-isolated vertices drawn from the seed (a pool of ``rhs_pool`` queries,
used in turn).  A set runs all its iterations with no tolerance stop
(GAP's ``pr`` caps PageRank at 20), so every seed does the same work.  The
graph comes from the configuration's ``graph_seed``: every seed serves the
same matrix.

Traffic keys: ``iterations_per_set``, ``sources_per_query``, ``rhs_pool``,
``check_sample``.
"""
from __future__ import annotations

import numpy as np

from bench.drivers import cg
from bench.gen import kronecker


class Driver(cg.Driver):
    def matrix(self) -> tuple[int, np.ndarray, np.ndarray, np.ndarray]:
        """The PPR matrix of the configuration's graph; keeps its degrees."""
        n, rows, cols, vals, self.degree = kronecker.graph500_ppr(self.cfg)
        return n, rows, cols, vals

    def right_hand_side(self, rng: np.random.Generator) -> np.ndarray:
        """One personalized query's ``b``, its sources drawn from ``rng``."""
        s = kronecker.personalization(self.degree, int(self.traffic["sources_per_query"]), rng)
        return kronecker.ppr_rhs(self.degree, s, float(self.cfg["damping"]))

    def xla_rescale(self) -> float:
        """The matrix's eigenvalues lie in ``[1 − α, 1 + α]``."""
        return 1.0 / (1.0 + float(self.cfg["damping"]))
