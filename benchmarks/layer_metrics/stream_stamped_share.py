"""stream_stamped_share: chunks whose rows were stamped on the device
over all chunks dispatched in the window (`StreamVerifier.chunks`)."""
LAYER = "stream pipeline"
UNIT, BETTER, SOURCE, MOVES = "%", "higher", "program_counter", "replay_rate"


def read(obs):
    chunks = obs.get("counters", {}).get("chunks")
    if not chunks or not sum(chunks.values()):
        return None
    return 100.0 * chunks["stamped"] / sum(chunks.values())
