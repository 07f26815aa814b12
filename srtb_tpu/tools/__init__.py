"""Command-line tools and soak harnesses."""

# The DM of the soak harnesses' synthetic streams (1405 + 64 MHz at
# 128 MSa/s; archive_replay, chaos_soak, crash_soak, fleet_soak).  Twice
# its sweep is 458 samples: the overlap-save reserve is an eighth of the
# smallest segment they are run at (2^12), so the detector's trim leaves
# three quarters of every series searched.  Not larger: from 0.04 on the
# reserve is half of a 2^12 segment or more, which SegmentProcessor
# refuses (pipeline/segment.refuse_overlong_reserve).
SOAK_DM = 0.01
