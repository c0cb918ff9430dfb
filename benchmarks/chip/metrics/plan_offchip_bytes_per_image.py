"""The deployed plan's predicted off-chip bytes per image
(``TrafficReport.offchip_bytes``): the paper's metric, as a count."""


def read(run):
    return run.plan_offchip_bytes_per_image
