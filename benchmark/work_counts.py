"""Operations and bytes the ALGORITHM requires, from the cell's shapes only:
the same whatever kernel implements the step. Not XLA's ``cost_analysis``
and not the one-hot matmul's 2 R (F B)(n V)."""

from __future__ import annotations

#: statistics carried per row by a histogram GBM: weight, gradient, hessian
GBM_STATS = 3


def gbm_tree(rows: int, features: int, depth: int) -> tuple[float, float]:
    """(operations, bytes) for ONE tree: at each level one pass over each
    row's stored codes (1 byte a feature), its node id (4 bytes) and its
    three f32 statistics, and one add per (row, feature, statistic)."""
    per_level_bytes = rows * (features * 1 + 4 + GBM_STATS * 4)
    per_level_ops = rows * features * GBM_STATS
    return float(depth * per_level_ops), float(depth * per_level_bytes)


def gbm_job(rows: int, features: int, depth: int,
            ntrees: int) -> tuple[float, float]:
    ops, nbytes = gbm_tree(rows, features, depth)
    return ntrees * ops, ntrees * nbytes


def glm_iteration(rows: int, p: int) -> tuple[float, float]:
    """(operations, bytes) for ONE IRLS iteration: one pass over the R x P
    f32 design (and the response), 2 R P^2 operations for the weighted Gram
    and 2 R P for its right-hand side, plus the P x P solve (2/3 P^3)."""
    ops = 2.0 * rows * p * p + 2.0 * rows * p + (2.0 / 3.0) * p ** 3
    nbytes = 4.0 * rows * (p + 1)
    return ops, nbytes


def glm_job(rows: int, p: int, iterations: int) -> tuple[float, float]:
    ops, nbytes = glm_iteration(rows, p)
    return iterations * ops, iterations * nbytes


def job_work(config: dict, iterations: int | None = None) -> tuple[float, float]:
    """Work of one training job of a configuration file."""
    d, p = config["data"], config["params"]
    if config["algo"] == "gbm":
        return gbm_job(d["rows"], d["features"], p["max_depth"], p["ntrees"])
    if config["algo"] == "glm":
        its = p["max_iterations"] if iterations is None else iterations
        return glm_job(d["rows"], d["features"] + 1, its)
    raise KeyError(f"no work count for algo {config['algo']!r}")
