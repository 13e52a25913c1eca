"""plane_land_lag_ms: how long a flush's verdicts lay ready on the chip
before the dispatcher came for them: for each `plane.land` event of the
traced window, its end minus the end of the last device operation of
the flight it waited for (0 where one still ran), median; from the
profiler's `.xplane.pb`, which holds the plane's stages beside the
device's operations. The device plane's stamps run ahead of the host
plane's there; the reader moves them back by what causality shows (no
operation started before its program was launched:
harness/plane_profile.py), so the lag is an upper estimate by a
launch's latency. Near 0 means the device sets the length of the
landing wait; milliseconds mean the host's readiness poll does. Nothing
without a trace, or where the profile holds no `plane.land` event."""
from harness import plane_profile

LAYER = "verify plane"
UNIT, BETTER, SOURCE, MOVES = "ms", "lower", "device_trace", "vote_p50_ms"


def read(obs):
    return plane_profile.land_lag_ms(obs)
