"""1 minus the union of device-operation intervals over the traced
window, in percent, averaged over the chips."""


def read(obs, args):
    tr = obs["trace"]
    return None if tr is None else 100.0 * tr.idle_share
