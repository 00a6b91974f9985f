"""Mean host time of a decode step in the window: the harness span
around ``_decode_once``, which ends in a copy to the host, over the
steps with no admission before them (whose span would also wait for the
prefills queued ahead of the step on the device)."""


def read(run):
    d = [b - a for a, b, _, _, admitted in run.window_steps() if not admitted]
    return 1e3 * sum(d) / len(d) if d else None
