"""Device time inside ``bench.prefill`` annotations (the model instance's
``prefill`` / ``prefill_suffix``) over the prefill launches of the traced
window. Source: device_trace."""

from harness.readers import prefill_device_ms as read  # noqa: E402,F401
