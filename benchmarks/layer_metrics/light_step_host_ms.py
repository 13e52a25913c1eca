"""light_step_host_ms: what a light step costs around its two commit
checks (the expiry and header checks, the validator-set root, the
errors' wrapping): each `light.step` of the window minus the
`light.trusting` and `light.new_set` stages that started inside it.
Median. Nothing on a program that has no such stage."""
from harness import stages, stats

LAYER = "light client"
UNIT, BETTER, SOURCE, MOVES = "ms", "lower", "program_span", "commit_p50_ms"
CHECKS = ("light.trusting", "light.new_set")


def read(obs):
    recs = stages.in_window(obs)
    steps = [r for r in recs or () if r[0] == "light.step"]
    checks = [r for r in recs or () if r[0] in CHECKS]
    if not steps:
        return None
    return stats.median([
        (dur - sum(c[2] for c in checks
                   if c[3] == tid and t0 <= c[1] <= t0 + dur)) / 1e6
        for _, t0, dur, tid in steps])
