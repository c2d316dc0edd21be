"""The tree learner: `schedule` (what a tree's program looks like for a
shape; no jax), `grow` (the jitted grower), `sweep` (many models in one
program). Nothing is imported here, so `schedule` loads without jax."""
