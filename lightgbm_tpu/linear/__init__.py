"""Piecewise-linear leaves (`linear_tree=true`).

The subsystem that upgrades constant leaf values to small per-leaf
linear models fitted on device (PAPERS.md: 1802.05640 — linear leaf
models cut iterations-to-accuracy on smooth targets, which compounds
here: fewer trees means faster training AND a smaller compiled forest
at serving/export time).

Layout:
- `solver.py`  — the batched per-leaf Newton-ridge fit: one vmapped
  `jnp.linalg.solve` over every leaf's small normal-equation system,
  built by one-hot MXU contractions over the leaf's top-k path
  features; constant-leaf fallback on singular/under-populated leaves.

The fit is a schedule-independent POST-GROWTH pass: tree structure and
gains come from the unchanged constant-leaf grower (matching the
reference `linear_tree`, which also fits after growth), and the solver
consumes only (leaf_id, raw X, grad, hess, bag weights) — arrays that
are already bit-identical across serial/data-parallel learner
schedules — so linear coefficients inherit every bit-identity
guarantee of the constant-leaf trees.
"""
from .solver import fit_leaves  # noqa: F401
