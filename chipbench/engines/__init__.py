"""Engine modes, one file per mode: ``Engine``."""
