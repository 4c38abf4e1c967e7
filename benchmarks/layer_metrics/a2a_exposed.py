"""Percent of the ``all-to-all`` time during which no other op ran on that
device: the part of the collective nothing hides."""


def read(obs):
    trace = obs.get("trace")
    if not trace or not trace["a2a_s"]:
        return None
    return 100.0 * trace["a2a_exposed_s"] / trace["a2a_s"]
