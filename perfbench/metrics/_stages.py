"""Shared by the stage-wall readers: seconds of one op in ``train_profile``
(``OpWorkflow.train(profile=True)``), summed over its stages of any kind
(fit, transform, substitute)."""


def stage_seconds(sources: dict, op: str):
    rows = [s for s in sources.get("stages") or [] if s["op"] == op]
    if not rows:
        return None
    return sum(s["wallSecs"] for s in rows)
