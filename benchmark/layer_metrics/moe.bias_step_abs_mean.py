"""The mean size of one selection-bias entry's step, over every routed
expert of every expert layer and the window's steps: what the rule that
balances the routers moved (`training/optim.router_bias_step`; the step's
`router_bias_step` counter). At a speed of 0.001 an entry moves by 0.001
less the layer's mean, so this reads just under 0.001 while the load is
uneven; 0 means the rule did not run. Nothing where the step returns no
such counter."""


def read(m):
    return getattr(m, "bias_step_abs_mean", None)
