"""Drafting: device self time of the ops under the program's
``draft_forward`` scope over the device's busy time in the traced window,
in %."""


def read(run):
    t = run.trace
    if not t or not t["busy_s"]:
        return None
    scopes = dict(t.get("device_scopes", ()))
    if "draft_forward" not in scopes:
        return None
    return 100.0 * scopes["draft_forward"] / t["busy_s"]
