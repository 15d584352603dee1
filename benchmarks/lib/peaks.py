"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` JAX reports.  A kind that is not here is an error: a
roofline or an MFU against a guessed peak is worth nothing.

Source: Google Cloud documentation, "TPU v5e" system architecture page:
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip,
1,600 Gbit/s of inter-chip interconnect.  JAX names that chip
"TPU v5 lite".  (Copied from deepspeed_tpu/telemetry/device.py, whose
substring matching and ``None`` default are not wanted here.)
"""

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "flops_int8": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e: 197 TFLOP/s "
                  "bf16, 819 GB/s, 16 GB)",
    },
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}. Add the chip to benchmarks/lib/peaks.py "
            "with its source before measuring on it.")
    return PEAKS[device_kind]
