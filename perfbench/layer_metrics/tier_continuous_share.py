"""Share of rollouts that took the paged continuous tier
(``GRPO.last_generation_info`` has ``slots``)."""


def read(ctx):
    steps = [r for r in ctx.records if "tier" in r]
    if not steps:
        return None
    return 100.0 * sum(r["tier"] == "continuous" for r in steps) / len(steps)
