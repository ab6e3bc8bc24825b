"""Published per-chip peaks, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
393 TOP/s in int8, 16 GB of HBM at 819 GB/s. A device kind missing here
is an error, not a default.
"""

PEAKS = {
    'TPU v5 lite': {'bf16_flop_s': 197e12, 'int8_op_s': 393e12,
                    'hbm_bytes': 16e9, 'hbm_bytes_s': 819e9},
}


def peaks(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f'no published peaks for device kind '
                       f'{device_kind!r}') from None
