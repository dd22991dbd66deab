"""One driver for each kind of deployment; a configuration's file names
its driver."""
