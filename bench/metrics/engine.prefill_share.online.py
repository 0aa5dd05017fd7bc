def read(rec):
    """Percent of the window spent in the joins' prefills."""
    if not rec.get("requests"):
        return None
    return 100.0 * rec["prefill_s"] / rec["window_s"]
