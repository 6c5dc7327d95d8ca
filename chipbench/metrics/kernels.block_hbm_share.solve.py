"""One coupled block-PCG iteration's least HBM bytes (blockbytes.py: x, z
and p of each component read and written once) at the chip's peak
bandwidth, over the device time of the program's Pallas kernels per
iteration, in percent, mean over the chips."""
import blockbytes
import metriclib


def read(run):
    ms = metriclib.per_iter_ms(run, "pallas_ns")
    if not ms or run.peak is None:
        return None
    chips = run.trace["n_devices"]
    least = blockbytes.least_bytes_per_iter(
        int(run.cfg.get("components", 1)), run.dofs / chips)
    return 100.0 * least / run.peak["hbm_bytes_per_s"] / (ms / 1e3)
