"""Entries the measured window drives, one module per traffic mode."""
