"""catchup_verify_share: the share of the engine's wall time spent
inside `verifier.verify` (the benchmark wraps the verifier it hands the
engine); the rest is reading, segmenting, applying and the cursor."""
LAYER = "stream pipeline"
UNIT, BETTER, SOURCE, MOVES = "%", "higher", "program_span", "replay_rate"


def read(obs):
    if not obs.get("engine_s") or "verify_s" not in obs:
        return None
    return 100.0 * obs["verify_s"] / obs["engine_s"]
