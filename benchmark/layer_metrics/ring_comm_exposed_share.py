"""Share of the traced window in which a collective occupies a device's op
line (ops there run one at a time, so no compute runs then), mean over the
devices. Source: device_trace."""


def read(run):
    red = run.reduced
    if red is None or len(red.devices) < 2:
        return None
    shares = [red.collective_ns(d) / 1e9 / red.window_s for d in red.devices]
    return 100.0 * sum(shares) / len(shares)
