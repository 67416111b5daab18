"""Device idle share in the traced window of the screening cell."""
from metrics._device_idle import read  # noqa: F401
