"""Seconds of host clock around the first call of the step program: its
compilation, or its load from the persistent cache, plus one step."""


def read(m):
    return m.compile_s
