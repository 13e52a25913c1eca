"""replay_rate: signatures in the blocks that `CatchupEngine` verified
and applied inside the window, over the seconds from the window's start
to the last of those blocks. Whole runs only: the run that straddles
the window's end counts neither its signatures nor its time, so the
rate does not step by one 64,000-signature run from run to run."""
UNIT, BETTER, SOURCE = "sigs/s", "higher", "host_clock"


def read(obs):
    if not obs.get("sigs_applied") or obs.get("engine_s", 0) <= 0:
        return None
    return obs["sigs_applied"] / obs["engine_s"]
