"""Share of a step's wall time outside ``get_action`` and ``learn``: reset,
decode to text, reward, ``assemble_learn_batch`` (host spans' clock)."""


def read(ctx):
    steps = [r for r in ctx.records if "rollout_s" in r]
    if not steps:
        return None
    total = sum(r["step_s"] for r in steps)
    inside = sum(r["rollout_s"] + r["learn_s"] for r in steps)
    return 100.0 * (total - inside) / total
