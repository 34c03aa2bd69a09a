"""`input_wait_share` (layer: input). Share of the window's wall time that the
step loop spent blocked on the next device batch: the `DevicePrefetcher`'s own
`wait_s` counter, read at the window's start and end, over the window."""


def read(results):
    if results["window_wait_s"] is None:
        return None
    return 100.0 * results["window_wait_s"] / results["window_s"]
