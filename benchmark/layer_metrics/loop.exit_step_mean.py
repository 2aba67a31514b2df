"""The mean exit step under the gate's distribution, `sum_r r x
exit_p_mean[r]` (r from 1), over the window's steps: what the gate does to
the weighting of the exits (1.875 of 4 where `lam` is 1/2 everywhere; towards
R as the gate learns to defer). From the step's counter `exit_p_mean`. None
where the step counts no such thing (a program without the family)."""


def read(m):
    return getattr(m, "exit_step_mean", None)
