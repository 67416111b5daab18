"""Device idle share in the traced window of a full-graph cell."""
from metrics._device_idle import read  # noqa: F401
