"""One module per kind of traffic; a cell file names its driver."""
