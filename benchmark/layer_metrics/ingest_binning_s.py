"""Host seconds around Dataset.construct(): sampling, bin bounds and
quantising (the dataset_* parts are on an earlier line of the run)."""

NAME = "ingest.binning_s"
UNIT = "s"
BETTER = "lower"
LAYER = "ingest"
SOURCE = "host_clock"
MOVES = "setup_s"
WORKLOADS = None


def read(r):
    return r.get("binning_s")
