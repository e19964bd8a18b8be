"""Operations and bytes counted from shapes, for rooflines and MFU:
``kernels.py`` per Pallas kernel call, and ``<reference>.py`` per whole
model step of one architecture (found by ``spec.work_module``)."""
