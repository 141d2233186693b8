"""Share of the HBM roofline that the save path's fingerprint dispatch
reaches: the bytes of the fingerprinted leaves, each read once, over the
chip's HBM bandwidth, divided by the device time of every program of the
fingerprint and diff dispatch (the digest programs, and on the chip the
comparison with the committed save's digests, which runs as its own
``not_equal`` program). The same bytes are counted whatever implements the
pass."""


def _is_fingerprint(name: str) -> bool:
    return ("_fp_pallas" in name or "_fp_jnp" in name or "_fp_diff" in name
            or name.startswith("jit_not_equal"))


def read(rec):
    if rec.trace is None or not rec.fingerprint_bytes:
        return None
    secs, n = rec.trace.module_time_s(_is_fingerprint)
    if n == 0 or secs <= 0:
        return None
    return 100.0 * rec.fingerprint_bytes / rec.peaks["hbm_bw"] / secs
