from repro.kernels.periodic.ops import periodic_correction_pallas

__all__ = ["periodic_correction_pallas"]
