"""Operations and bytes counted from shapes, for rooflines and MFU."""
