"""A scope's share of the first chip's busy time, for the readers of a
family whose driver leaves ``lib/xplane_scopes.by_pattern``'s result under
``run.facts["scopes"]``."""


def read(run, pattern: str):
    """Device time (%) of the ops under ``pattern`` (one of the patterns
    the driver asked for) over busy time; None where nothing was traced."""
    scopes = run.facts.get("scopes")
    if not scopes or not scopes.get("busy_s"):
        return None
    return 100.0 * scopes[pattern]["seconds"] / scopes["busy_s"]
