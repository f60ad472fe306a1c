"""The GLASS serving benchmark: one harness, data files per configuration,
traffic mix and cell, one reader per per-layer metric."""
