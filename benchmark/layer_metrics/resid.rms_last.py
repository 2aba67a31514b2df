"""The window's mean `resid_rms_last`: the RMS over the width of the
residual stream that enters the final norm, a mean over a step's tokens
(float32; the step's counter, models/ssm_dense.py), averaged over the
window's steps. It is what the embedding's multiplier (x 12) and the
residual's (x 0.22 on both of a layer's sublayers) exist to hold steady, and
the first number to move if either is lost: about 1.3 on fresh weights at
the published scalars, a tenth of that without the embedding's. None where
the step counts no such thing (a program without the family, another
family's runner)."""


def read(m):
    return getattr(m, "resid_rms_last", None)
