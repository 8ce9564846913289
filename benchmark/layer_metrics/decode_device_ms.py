"""Device time inside ``bench.decode`` annotations (``decode_step``) over
the decode launches of the traced window. Source: device_trace."""


def read(run):
    red = run.reduced
    if red is None or not red.launches("bench.decode"):
        return None
    return red.device_ns_in("bench.decode") / red.launches("bench.decode") / 1e6
