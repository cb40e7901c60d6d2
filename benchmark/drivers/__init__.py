"""Drivers: one per kind of cell, found by the name in the cell's file."""
