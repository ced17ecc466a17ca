"""Launch layer: production mesh and the train driver."""
