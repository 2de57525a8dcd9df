"""One driver per kind of traffic. A mix's ``kind`` names the driver that
feeds it to the system under test; a cell of an existing kind is data only."""
