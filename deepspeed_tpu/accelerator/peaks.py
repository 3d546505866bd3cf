"""Published per-chip peak rates, keyed by ``jax.devices()[0].device_kind``.

The one table every utilisation figure in the repo divides by (``bench.py``,
``chip_smoke.py``, ``tools/``). A device that is not listed raises: a peak
assumed for an unknown chip turns every MFU / roofline share derived from it
into fiction. Kind strings are the ones libtpu reports (the same spellings
``jax/_src/pallas/mosaic/tpu_info.py`` matches on); ``"TPU v5 lite"`` is what
libtpu 0.0.34 reports for a v5e.

Sources: Google Cloud TPU documentation, system-architecture pages "TPU v4",
"TPU v5e", "TPU v5p", "TPU v6e" (bf16 peak compute, HBM capacity, HBM
bandwidth per chip).
"""

import typing


class DevicePeaks(typing.NamedTuple):
    bf16_tflops: float   # dense bf16 matmul peak, TFLOP/s per chip
    hbm_gbs: float       # HBM bandwidth, GB/s per chip
    hbm_gib: float       # HBM capacity per chip


DEVICE_PEAKS = {
    "TPU v4": DevicePeaks(275.0, 1228.0, 32.0),
    "TPU v5 lite": DevicePeaks(197.0, 819.0, 16.0),
    "TPU v5e": DevicePeaks(197.0, 819.0, 16.0),
    "TPU v5": DevicePeaks(459.0, 2765.0, 95.0),
    "TPU v5p": DevicePeaks(459.0, 2765.0, 95.0),
    "TPU v6 lite": DevicePeaks(918.0, 1640.0, 32.0),
    "TPU v6e": DevicePeaks(918.0, 1640.0, 32.0),
}


def device_peaks(device_kind):
    """``DevicePeaks`` for ``device_kind``; an unknown kind is an error."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak rates for device_kind {device_kind!r}; add it "
            f"to deepspeed_tpu/accelerator/peaks.py with its source (known: "
            f"{sorted(DEVICE_PEAKS)})") from None
