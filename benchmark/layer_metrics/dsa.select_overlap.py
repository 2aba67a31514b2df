"""Share of the (row, key) pairs the PROGRAM chose on the check batch that
the float32 reference chose too, the worst layer (the program's sets from
`make_probe`, the kernels' own score and thresholds; the reference's from
`lax.top_k` on its own float32 score of its own float32 hidden states).
Under 1.0 in a sound run: the program's score has bfloat16 operands and a
choice is discontinuous, so keys at a row's margin change sides. Nothing
where the runner's check made no such comparison."""


def read(m):
    return getattr(m, "dsa_select_overlap", None)
