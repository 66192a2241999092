"""Device time of the DAS extension per acknowledged put in the window, in ms.

A put's square is extended by two products on the bit-matrix GF kernel,
whose jitted program is named ``gf_bitmatmul``: its device seconds in the
trace over the window's acknowledged puts.  Nothing to read (no trace, no
such program, as in a program without that kernel, or no put): None.
"""


def read(r):
    if r.trace is None:
        return None
    seconds = r.trace.program_seconds("gf_bitmatmul")
    puts = sum(d.error is None for d in r.done)
    if seconds <= 0 or not puts:
        return None
    return seconds * 1e3 / puts
