"""Host seconds of ``build_blocked`` for every layout the cell reads, and
of placing them and the flat graph on the device."""


def read(run):
    if "build_blocked_s" not in run.phases:
        return None
    return run.phases["build_blocked_s"] + run.phases["place_s"]
