"""The benchmark's own library: everything a later PR may not change."""
