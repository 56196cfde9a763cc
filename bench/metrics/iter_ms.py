"""Host wall time per loop iteration of the serving dispatches (ms): the
sum over the window's dispatches of the time from launch to the
readback of their collect, over the sum of the loop iterations they
ran (the server's ``lane_log`` measure, taken per window)."""


def read(record):
    d = record["dispatches"]
    iters = sum(it for _, _, it in d)
    if not iters:
        return None
    return 1e3 * sum(wall for wall, _, _ in d) / iters
