"""The host-loop solvers: consensus-ADMM (solvers/admm.py) and distributed
block coordinate descent (solvers/block_cd.py).

Each runs a host-side outer loop around one compiled step program, so it
cannot execute inside a traced solve; ``optim.problem.choose_solver`` names
it (``OptimizerConfig.solver``) and :data:`HOST_SOLVERS` maps the name to
its factory, ``factory(problem, dist, mesh, l1_mask) → solve_fn(lam,
w_prev, dist_override=None)``.  ``solvers/sharded.py`` runs a factory's
solves as a warm-started λ grid.  The four on-device solvers (L-BFGS,
OWL-QN, TRON, SPG) are ``optim``'s own.
"""

from photon_ml_tpu.solvers import admm, block_cd

HOST_SOLVERS = {
    "admm": admm.make_sharded_solver,
    "block_cd": block_cd.make_sharded_solver,
}
