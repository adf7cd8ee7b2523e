"""repro — quorum all-pairs reproduction (see DESIGN.md)."""
