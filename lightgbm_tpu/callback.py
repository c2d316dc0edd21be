"""Training callbacks.

Mirrors the reference python-package callback protocol
(`python-package/lightgbm/callback.py`): callbacks receive a CallbackEnv
namedtuple before/after each iteration; `EarlyStopException` unwinds the
training loop (engine.py:216-218 in the reference).
"""
from __future__ import annotations

import collections
from typing import Callable, List

CallbackEnv = collections.namedtuple(
    "CallbackEnv",
    ["model", "params", "iteration", "begin_iteration", "end_iteration",
     "evaluation_result_list"])


class EarlyStopException(Exception):
    def __init__(self, best_iteration: int, best_score):
        super().__init__()
        self.best_iteration = best_iteration
        self.best_score = best_score


def print_evaluation(period: int = 1, show_stdv: bool = True) -> Callable:
    """Reference: callback.py print_evaluation."""
    def _callback(env: CallbackEnv) -> None:
        if period > 0 and env.evaluation_result_list \
                and (env.iteration + 1) % period == 0:
            result = "\t".join(
                [_format_eval_result(x, show_stdv) for x in env.evaluation_result_list])
            from . import log
            log.info("[%d]\t%s", env.iteration + 1, result)
    _callback.order = 10
    return _callback


def _format_eval_result(value, show_stdv: bool = True) -> str:
    if len(value) == 4:
        return f"{value[0]}'s {value[1]}: {value[2]:g}"
    if len(value) == 5:
        if show_stdv:
            return f"{value[0]}'s {value[1]}: {value[2]:g} + {value[4]:g}"
        return f"{value[0]}'s {value[1]}: {value[2]:g}"
    raise ValueError("Wrong metric value")


def record_evaluation(eval_result: dict) -> Callable:
    """Reference: callback.py record_evaluation."""
    if not isinstance(eval_result, dict):
        raise TypeError("eval_result should be a dict")
    eval_result.clear()

    def _init(env: CallbackEnv) -> None:
        for data_name, eval_name, _, _ in env.evaluation_result_list:
            eval_result.setdefault(data_name, collections.OrderedDict())
            eval_result[data_name].setdefault(eval_name, [])

    def _callback(env: CallbackEnv) -> None:
        if not eval_result:
            _init(env)
        for data_name, eval_name, result, _ in env.evaluation_result_list:
            eval_result.setdefault(data_name, collections.OrderedDict())
            eval_result[data_name].setdefault(eval_name, [])
            eval_result[data_name][eval_name].append(result)

    # checkpoint/resume protocol: a resumed run must re-enter the loop
    # with the recorded history of the interrupted one, or the user's
    # evals_result dict restarts mid-run with a hole in every series
    def _state() -> dict:
        return {d: {m: list(v) for m, v in metrics.items()}
                for d, metrics in eval_result.items()}

    def _restore(state: dict) -> None:
        eval_result.clear()
        for d, metrics in state.items():
            eval_result[d] = collections.OrderedDict(
                (m, [float(x) for x in v]) for m, v in metrics.items())

    _callback.order = 20
    _callback.checkpoint_key = "record_evaluation"
    _callback.checkpoint_state = _state
    _callback.restore_state = _restore
    return _callback


def reset_parameter(**kwargs) -> Callable:
    """Reference: callback.py reset_parameter (supports learning_rate
    schedules as list or callable)."""
    def _callback(env: CallbackEnv) -> None:
        new_params = {}
        for key, value in kwargs.items():
            if isinstance(value, list):
                if len(value) != env.end_iteration - env.begin_iteration:
                    raise ValueError(f"Length of list {key} has to equal num_boost_round")
                new_params[key] = value[env.iteration - env.begin_iteration]
            elif callable(value):
                new_params[key] = value(env.iteration - env.begin_iteration)
        if new_params:
            if "learning_rate" in new_params:
                env.model._inner.shrinkage_rate = float(new_params["learning_rate"])
            env.params.update(new_params)
    _callback.before_iteration = True
    _callback.order = 10
    return _callback


def checkpoint(save_fn: Callable, interval: int = 1) -> Callable:
    """Periodic full-state snapshot (preemption tolerance). `save_fn(env)`
    builds and writes the snapshot — `lightgbm_tpu.engine` wires it to a
    `checkpoint.CheckpointManager`. Runs AFTER early_stopping (order 40)
    so a restored snapshot carries the patience state of its own
    iteration, not the previous one.

    A failed WRITE is logged and training continues: losing one snapshot
    (the previous one still restores) is strictly better than killing a
    long run over a transient filesystem error. Only IO-shaped errors
    are swallowed — anything else (e.g. the non-finite-gradient guard
    firing inside the state capture's pipeline flush) is a training
    error and must propagate."""
    def _callback(env: CallbackEnv) -> None:
        if interval > 0 and (env.iteration + 1) % interval == 0:
            from . import telemetry
            from .checkpoint import CheckpointError
            from .testing.faults import InjectedFault
            try:
                # timed as its own phase: snapshots drain the async tree
                # pipeline, so their cost must not masquerade as tree/grow
                with telemetry.span("checkpoint/save"):
                    save_fn(env)
            except (OSError, CheckpointError, InjectedFault) as exc:
                # deliberately NOT RuntimeError: jax backend failures
                # (XlaRuntimeError) during the state capture's pipeline
                # flush mean the training state itself is suspect
                from . import log
                log.warning("Checkpoint write failed at iteration %d "
                            "(%s: %s); continuing without it",
                            env.iteration + 1, type(exc).__name__, exc)
    _callback.order = 40
    return _callback


def early_stopping(stopping_rounds: int, verbose: bool = True) -> Callable:
    """Reference: callback.py early_stopping."""
    best_score: List[float] = []
    best_iter: List[int] = []
    best_score_list: List = []
    cmp_op: List[Callable] = []
    higher_better: List[bool] = []

    def _init(env: CallbackEnv) -> None:
        if not env.evaluation_result_list:
            raise ValueError("For early stopping, at least one dataset and "
                             "eval metric is required for evaluation")
        for _ in env.evaluation_result_list:
            best_iter.append(0)
            best_score_list.append(None)

        for _, _, _, is_higher_better in env.evaluation_result_list:
            higher_better.append(bool(is_higher_better))
            if is_higher_better:
                best_score.append(float("-inf"))
                cmp_op.append(lambda a, b: a > b)
            else:
                best_score.append(float("inf"))
                cmp_op.append(lambda a, b: a < b)

    def _callback(env: CallbackEnv) -> None:
        if not cmp_op:
            _init(env)
        for i, (data_name, eval_name, score, _) in enumerate(env.evaluation_result_list):
            if best_score_list[i] is None or cmp_op[i](score, best_score[i]):
                best_score[i] = score
                best_iter[i] = env.iteration
                best_score_list[i] = env.evaluation_result_list
            elif env.iteration - best_iter[i] >= stopping_rounds:
                if verbose:
                    from . import log
                    log.info("Early stopping, best iteration is: [%d]",
                             best_iter[i] + 1)
                raise EarlyStopException(best_iter[i], best_score_list[i])

    # checkpoint/resume protocol: without the best-score history a
    # resumed run would reset its patience counter and stop late (or,
    # with a restarted best_score baseline, stop on the wrong iteration)
    def _state() -> dict:
        return {
            "best_score": list(best_score),
            "best_iter": [int(x) for x in best_iter],
            "best_score_list": [
                None if lst is None else [[d, m, float(v), bool(b)]
                                          for d, m, v, b in lst]
                for lst in best_score_list],
            "higher_better": list(higher_better),
        }

    def _restore(state: dict) -> None:
        best_score[:] = [float(x) for x in state["best_score"]]
        best_iter[:] = [int(x) for x in state["best_iter"]]
        best_score_list[:] = [
            None if lst is None else [(d, m, float(v), bool(b))
                                      for d, m, v, b in lst]
            for lst in state["best_score_list"]]
        higher_better[:] = [bool(x) for x in state["higher_better"]]
        cmp_op[:] = [(lambda a, b: a > b) if hb else (lambda a, b: a < b)
                     for hb in higher_better]

    _callback.order = 30
    _callback.checkpoint_key = "early_stopping"
    _callback.checkpoint_state = _state
    _callback.restore_state = _restore
    return _callback
