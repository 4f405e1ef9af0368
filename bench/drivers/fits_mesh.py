"""Whole Laplace GP-classification fits over a mesh of chips, back to back.

The fits of ``bench/drivers/fits.py`` (``Fits``: the same front door, row
orders, window, kept systems and check), each run as ``laplace_gpc(...,
mesh=make_solve_mesh(mesh_devices))``: the rows of ``x``, ``y`` and ``f``
are split over a 1-D ``"solve"`` mesh of the traffic's ``mesh_devices``
chips, every Newton system is solved by the sharded def-CG with its
recycled state carried sharded, and the driver's two Gram passes a system
run split over the chips.

Each row order is placed on the mesh once, at set-up (``laplace.place``):
a fit then moves no data, as in a deployment that keeps its data where it
is solved.  The mesh takes the first ``mesh_devices`` devices; a machine
with fewer fails at set-up.
"""

from __future__ import annotations

import functools

from bench import harness
from bench.drivers.fits import Fits, attempted_failed, end_to_end  # noqa: F401


class MeshFits(Fits):
    def __init__(self, config: dict, traffic: dict, seed: int,
                 impl: str = "auto"):
        super().__init__(config, traffic, seed, impl)
        from repro.launch.mesh import make_solve_mesh

        self.mesh = make_solve_mesh(traffic["mesh_devices"])
        self.orders = [
            self.laplace.place(self.mesh, x, y)[:2] for x, y in self.orders
        ]

    def _fit(self, max_newton: int):
        front_door = functools.partial(self.laplace.laplace_gpc, mesh=self.mesh)
        with harness.patched(self.laplace, laplace_gpc=front_door):
            return super()._fit(max_newton)


def make(config, traffic, seed, impl="auto"):
    return MeshFits(config, traffic, seed, impl)
