"""On-chip serving benchmark: one harness, cells defined by data files."""
